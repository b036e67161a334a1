"""Divisibility-aware sharding policies.

The production mesh is fixed at (data=16, model=16) (+pod=2), but the assigned
architectures have head counts like 25 (hymba) and 4 (xlstm) and vocabs like
49 155 (granite) that do not divide 16.  Rather than hand-tuning each arch,
every tensor dimension asks the policy: *shard over this axis iff divisible*,
else fall back (replicate, or shard an alternative dimension).  Vocab is
handled by padding to a lane-and-axis multiple (see ``pad_vocab``) so the
embedding/logits shards stay dense.

``MeshAxes`` carries axis names + sizes so the same model code lowers on both
the single-pod and multi-pod meshes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map  # noqa: F401 (re-exported)

SHARD_MAP_KW = {"check_vma": False}


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical→physical axis mapping for one mesh."""

    batch: tuple                    # e.g. ("data",) or ("pod", "data")
    model: str                      # "model"
    sizes: dict                     # axis name → size
    fsdp: Optional[str] = "data"    # axis for 2-D (FSDP) param sharding; None = off
    tp: bool = True                 # tensor-parallel over 'model'; False → the
                                    # model axis joins the batch axes (DP-only,
                                    # right for sub-1B archs where TP shards
                                    # are tiny and collectives dominate)

    @property
    def batch_size(self) -> int:
        out = 1
        for a in self.batch:
            out *= self.sizes[a]
        return out

    @property
    def model_size(self) -> int:
        return self.sizes[self.model]

    @property
    def tp_axis(self) -> Optional[str]:
        return self.model if self.tp else None

    def shard_if(self, dim: int, axis: Optional[str] = None):
        """Return the model axis name iff ``dim`` divides evenly, else None."""
        if not self.tp and (axis is None or axis == self.model):
            return None
        axis = axis or self.model
        size = self.sizes[axis] if isinstance(axis, str) else 1
        return axis if dim % size == 0 and dim >= size else None

    def fsdp_if(self, dim: int):
        """FSDP axis iff enabled and ``dim`` divides: params gain a second
        shard dim so 67–72 B-param archs fit 16 GB/chip (weights gathered
        just-in-time by XLA SPMD — the ZeRO-3 pattern)."""
        if self.fsdp is None:
            return None
        size = self.sizes.get(self.fsdp, 1)
        return self.fsdp if dim % size == 0 and dim >= size else None

    def batch_if(self, dim: int):
        """Batch axes iff divisible by the full batch extent, else None."""
        return self.batch if dim % self.batch_size == 0 and dim >= self.batch_size else None

    def batch_axes_for(self, dim: int):
        """Largest-product subset of the batch axes dividing ``dim``.

        A greedy prefix is not enough: whisper's global batch 256 on the
        2×16×16 DP-only mesh must pick (data, model)=256 and leave 'pod'
        idle, not the prefix (pod, data)=32 — the latter was an 8× per-device
        activation blowup (87 GB/chip, EXPERIMENTS.md §Dry-run)."""
        best, best_prod = None, 0
        n = len(self.batch)
        for mask in range(1, 1 << n):
            axes = tuple(self.batch[i] for i in range(n) if mask >> i & 1)
            prod = 1
            for a in axes:
                prod *= self.sizes[a]
            if dim % prod == 0 and prod > best_prod:
                best, best_prod = axes, prod
        return best


def from_mesh(mesh: Mesh, *, fsdp: bool = True, tp: bool = True) -> MeshAxes:
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    batch = tuple(n for n in names if n in ("pod", "data"))
    if not tp:
        batch = batch + ("model",)   # DP-only: model axis carries batch
    return MeshAxes(
        batch=batch, model="model", sizes=sizes,
        fsdp="data" if fsdp else None, tp=tp,
    )


def single_device_axes() -> MeshAxes:
    """Degenerate axes for smoke tests on one device (everything replicated)."""
    return MeshAxes(batch=("data",), model="model", sizes={"data": 1, "model": 1})


def free_model_seq(axes: MeshAxes, batch_dim: int, seq_dim: int):
    """Sequence-parallel axis when 'model' is not already carrying batch.

    DP-only archs (whisper, xlstm) leave the model axis idle whenever the
    batch does not divide onto it (prefill_32k batch 32 < 256): sharding the
    sequence over that free axis recovers the 16× (§Perf iteration W1)."""
    ba = axes.batch_axes_for(batch_dim) or ()
    if axes.model in ba:
        return None
    m = axes.model_size
    return axes.model if (seq_dim % m == 0 and seq_dim >= m) else None


def constrain(x, spec: P):
    """``with_sharding_constraint`` that no-ops outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def pad_to_multiple(dim: int, size: int) -> int:
    """Round ``dim`` up to a multiple of ``size`` (the divisibility policy's
    other arm: when a dimension *must* shard, pad it dense instead of
    replicating — vocab padding and the dist executor's record/tree padding
    both go through here)."""
    if size <= 1:
        return dim
    return ((dim + size - 1) // size) * size


def pad_vocab(vocab: int, axes: MeshAxes, lane: int = 128) -> int:
    """Pad the vocabulary so it shards densely: multiple of lane·|model|."""
    return pad_to_multiple(vocab, lane * (axes.model_size if axes.tp else 1))


def forest_mesh(record_shards: int, tree_shards: int, devices=None) -> Mesh:
    """(records × trees) mesh over the first R·G devices.

    The ``repro.dist`` layout: axis ``"records"`` carries the data
    decomposition (the §3.6 M/P slicing lifted to devices), axis ``"trees"``
    carries the forest.  Plans may use fewer devices than the host exposes
    (a feasibility-clamped plan on a small workload), so this builds the
    mesh explicitly rather than via ``jax.make_mesh``.
    """
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    need = record_shards * tree_shards
    if need > len(devs):
        raise ValueError(f"plan needs {need} devices, host has {len(devs)}")
    grid = np.array(devs[:need], dtype=object).reshape(record_shards, tree_shards)
    return Mesh(grid, ("records", "trees"))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


# ---------------------------------------------------------------------------
# Canonical spec builders (dims listed logically; scan adds a leading L=None)
# ---------------------------------------------------------------------------


def attn_specs(
    axes: MeshAxes, n_heads: int, n_kv_heads: int, d_model: int = 0,
    head_dim: int = 0,
) -> dict:
    """QKV/O projection *storage* specs: 2-D (data × model) sharding of the
    flattened weight dims.  Storage sharding is decoupled from compute: the
    attention math runs on (batch × sequence)-sharded activations and XLA
    gathers the bf16 weights just-in-time (ZeRO-3) — so the model axis can
    shard the flattened H·hd dim even when the *head count* does not divide
    the mesh (deepseek-67b kv=8, hymba 25H, yi kv=4...)."""
    d = axes.fsdp_if(d_model) if d_model else None
    hd = head_dim or (d_model // max(n_heads, 1) if d_model else 0)
    q_out = axes.shard_if(n_heads * hd) if hd else axes.shard_if(n_heads)
    kv_out = axes.shard_if(n_kv_heads * hd) if hd else axes.shard_if(n_kv_heads)
    return {
        "wq": P(d, q_out),       # (D, H·hd) — flattened projection dims
        "wk": P(d, kv_out),
        "wv": P(d, kv_out),
        "wo": P(q_out, d),       # (H·hd, D)
    }


def mlp_specs(axes: MeshAxes, d_ff: int, d_model: int = 0) -> dict:
    f = axes.shard_if(d_ff)
    d = axes.fsdp_if(d_model) if d_model else None
    return {"wi": P(d, f), "wg": P(d, f), "wo": P(f, d)}


def moe_specs(axes: MeshAxes, n_experts: int, d_ff: int, d_model: int = 0) -> dict:
    e = axes.shard_if(n_experts)
    f = axes.shard_if(d_ff) if e is None else None  # EP first; else TP inside experts
    d = axes.fsdp_if(d_model) if d_model else None
    return {
        "wi": P(e, d, f),        # (E, D, F)
        "wg": P(e, d, f),
        "wo": P(e, f, d),        # (E, F, D)
    }


def embed_specs(axes: MeshAxes, d_model: int = 0) -> dict:
    d = axes.fsdp_if(d_model) if d_model else None
    return {"table": P(axes.model, d)}   # (V_padded, D): vocab-sharded


def norm_specs() -> dict:
    return {"scale": P(None)}


def prepend(spec_tree, extra=None):
    """Add a leading (layer-stack) dim to every spec in a tree."""
    return jax.tree.map(
        lambda s: P(extra, *s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def batch_spec(axes: MeshAxes, *rest) -> P:
    return P(axes.batch, *rest)


def zero1_spec(spec: P, shape: Sequence[int], axes: MeshAxes) -> P:
    """ZeRO-1: additionally shard the largest unsharded dim over 'data'.

    Optimizer-state tensors follow their parameter spec; any dim not already
    sharded is a candidate for slicing over the data axis (classic optimizer
    state sharding).  Falls back to the parameter spec when nothing divides.
    """
    data = "data"
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    if data in used:
        return spec
    n = axes.sizes.get(data, 1)
    best_dim, best_size = -1, 0
    for i, d in enumerate(shape):
        taken = spec[i] if i < len(spec) else None
        if taken is None and d % n == 0 and d > best_size and d >= n:
            best_dim, best_size = i, d
    if best_dim < 0:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    parts[best_dim] = data
    return P(*parts)
