"""Procedures 4 & 5: speculative parallel tree evaluation in JAX.

The paper's core contribution.  For each record, *every node of the tree* is
evaluated speculatively in one branch-free vector step, producing a successor
array ``path`` (leaves self-loop).  The root's eventual successor — the
record's terminal leaf — is then found by **pointer jumping**
(``path[i] = path[path[i]]``), needing only ``Θ(log₂ d)`` rounds instead of a
``d``-step descent.

Mapping to TPU (vs. the paper's CUDA record groups):
  * record group of p = N CUDA threads  →  one row of a (records × nodes) tile;
    nodes live on the 128-lane axis, records on the sublane axis.
  * shared-memory ``path`` + barrier()   →  a (M, N) register/VMEM array; tile
    lanes are lock-step so the warp-synchronous barrier elision in the paper's
    EvalTreeByNode is implicit and free.
  * node-eval attribute gather           →  either a vectorized gather
    (``records[:, attr_idx]``) or a one-hot MXU matmul (see kernels/tree_eval).
  * multi-jump per loop (Procedure 5 line 20) → ``jumps_per_round``.

Procedure-5 improvements implemented here:
  * leaves are pre-initialised from the static ``leafPaths`` table and only
    internal nodes are (re)computed — ``internal_only=True``;
  * several pointer jumps per synchronisation round (``jumps_per_round``);
  * the processor→node map exists implicitly: we compute internal-node
    successors with a mask rather than per-lane index tables, which is the
    natural SIMD-register formulation.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.tree import BOTTOM, EncodedTree


def _tree_arrays(enc: EncodedTree):
    return (
        jnp.asarray(enc.attr_idx, jnp.int32),
        jnp.asarray(enc.threshold, jnp.float32),
        jnp.asarray(enc.child, jnp.int32),
        jnp.asarray(enc.class_val, jnp.int32),
    )


_F32_MAX = float(jnp.finfo(jnp.float32).max)


def sanitize_records(records: jax.Array) -> jax.Array:
    """Make a record batch safe for one-hot-matmul node evaluation.

    The MXU formulation ``vals = records @ S`` multiplies every attribute by
    0 or 1 and sums, so a single non-finite attribute poisons the whole row
    (IEEE ``inf * 0 = NaN``).  Clamping preserves routing against every
    finite threshold: NaN and -FLT_MAX both fail ``v > t`` for all reachable
    thresholds, ±inf route exactly like ±FLT_MAX, and the leaf self-loop's
    +inf threshold still rejects everything.  Gather-based evaluators don't
    need this — they read only the addressed attribute.
    """
    records = jnp.asarray(records, jnp.float32)
    return jnp.where(
        jnp.isnan(records), -_F32_MAX, jnp.clip(records, -_F32_MAX, _F32_MAX)
    )


def speculative_node_eval(
    records: jax.Array,
    attr_idx: jax.Array,
    threshold: jax.Array,
    child: jax.Array,
    *,
    use_onehot_matmul: bool = False,
    attr_select: jax.Array | None = None,
) -> jax.Array:
    """Evaluate every node against every record (the speculative step).

    Returns ``path`` (M, N) int32: the successor of node ``n`` for record
    ``m`` — ``child[n] + (r[attr[n]] > threshold[n])``.  Leaves self-loop by
    construction of the encoding (+inf thresholds).

    ``use_onehot_matmul`` selects the MXU formulation
    ``vals = records @ S`` with ``S[a, n] = 1⇔attr[n]==a`` — on TPU this
    replaces a cross-lane gather with a systolic matmul; on CPU it is the
    same arithmetic.
    """
    if use_onehot_matmul:
        records = sanitize_records(records)
        if attr_select is None:
            n_attrs = records.shape[-1]
            attr_select = jax.nn.one_hot(attr_idx, n_attrs, dtype=records.dtype).T
        # HIGHEST: on TPU the default f32 matmul is one bf16 pass, which
        # would round the attribute values the one-hot product must copy
        vals = jnp.dot(records, attr_select, precision=jax.lax.Precision.HIGHEST)  # (M, N)
    else:
        vals = records[:, attr_idx]  # (M, N) gather
    return child[None, :] + (vals > threshold[None, :]).astype(jnp.int32)


def pointer_jump(path: jax.Array, rounds: int, jumps_per_round: int = 1) -> jax.Array:
    """Parallel path reduction: ``path[i] ← path[path[i]]`` (Procedure 4 l.15).

    ``jumps_per_round`` > 1 is Procedure 5's multi-reduction optimisation
    (line 20, ``path[path[path[i]]]``): fewer synchronisation rounds when the
    average traversal depth d_µ exceeds the per-round doubling.
    """
    def one_round(p):
        for _ in range(jumps_per_round):
            p = jnp.take_along_axis(p, p, axis=1)
        return p

    return jax.lax.fori_loop(0, rounds, lambda _, p: one_round(p), path)


def rounds_for_depth(max_depth: int, jumps_per_round: int = 1) -> int:
    """Pointer-jump rounds guaranteeing root→leaf convergence.

    After ``j`` total jumps every pointer skips ``2^j`` original steps, so we
    need ``2^(rounds·k) ≥ max_depth`` where each round applies ``k`` jumps...
    careful: ``k`` jumps inside one round compose as ``2^k`` doubling only in
    terms of *jump applications*; total applications = rounds·k and coverage
    is ``2^(rounds·k)``.  We need ``2^(rounds·k) ≥ max_depth``.
    """
    if max_depth <= 1:
        return 1
    total_jumps = max(1, math.ceil(math.log2(max_depth)))
    return math.ceil(total_jumps / jumps_per_round)


@partial(jax.jit, static_argnames=("max_depth", "jumps_per_round", "use_onehot_matmul", "early_exit"))
def eval_speculative(
    records: jax.Array,
    attr_idx: jax.Array,
    threshold: jax.Array,
    child: jax.Array,
    class_val: jax.Array,
    *,
    max_depth: int,
    jumps_per_round: int = 2,
    use_onehot_matmul: bool = False,
    early_exit: bool = False,
) -> jax.Array:
    """Procedure 4/5: speculative node evaluation + pointer-jump reduction.

    Args:
      records: (M, A) float array.
      max_depth: static tree-depth bound.
      jumps_per_round: Procedure-5 multi-jump factor (paper found 2 optimal).
      use_onehot_matmul: MXU-friendly node evaluation.
      early_exit: use a while-loop testing ``class[path[:,0]] ≠ ⊥`` for every
        record (Procedure 4 line 14) instead of the static round bound.

    Returns:
      (M,) int32 class assignments.
    """
    path = speculative_node_eval(
        records, attr_idx, threshold, child, use_onehot_matmul=use_onehot_matmul
    )

    if early_exit:

        def cond(p):
            return jnp.any(class_val[p[:, 0]] == BOTTOM)

        def body(p):
            for _ in range(jumps_per_round):
                p = jnp.take_along_axis(p, p, axis=1)
            return p

        path = jax.lax.while_loop(cond, body, path)
    else:
        path = pointer_jump(path, rounds_for_depth(max_depth, jumps_per_round), jumps_per_round)
    return class_val[path[:, 0]]


def eval_speculative_tree(
    enc: EncodedTree,
    records,
    *,
    max_depth: int,
    jumps_per_round: int = 2,
    use_onehot_matmul: bool = False,
    early_exit: bool = False,
):
    """Convenience wrapper taking an :class:`EncodedTree`."""
    a, t, c, k = _tree_arrays(enc)
    return eval_speculative(
        jnp.asarray(records, jnp.float32),
        a,
        t,
        c,
        k,
        max_depth=max_depth,
        jumps_per_round=jumps_per_round,
        use_onehot_matmul=use_onehot_matmul,
        early_exit=early_exit,
    )


def shard_eval_speculative(
    enc: EncodedTree,
    records,
    *,
    max_depth: int,
    mesh,
    axis: str = "data",
    jumps_per_round: int = 2,
):
    """Record groups sharded over the mesh ``axis``; tree replicated.

    Each device holds G/|axis| record groups — the paper's grid of record
    groups mapped onto the device mesh; ``path`` never leaves a device
    (it is the shared-memory analogue), so the only collective traffic is
    the record scatter / class gather, i.e. the paper's t_s(M) term.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    a, t, c, k = _tree_arrays(enc)
    rec = jnp.asarray(records, jnp.float32)
    fn = jax.jit(
        partial(
            eval_speculative,
            max_depth=max_depth,
            jumps_per_round=jumps_per_round,
            use_onehot_matmul=True,
        ),
        in_shardings=(
            NamedSharding(mesh, P(axis, None)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P(axis)),
    )
    return fn(rec, a, t, c, k)
