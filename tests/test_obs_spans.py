"""The request path's phase spans and transfer counters.

A traced ``TreeServeEngine`` wave must split ``kernel.dispatch`` into its
phases (the host-to-device copy, resolution with the one pack of the tree's
tables, bucket padding, the variant call with its one jitted call, the
device wait and the copy back) and frame it with ``serve.batch`` and
``serve.hooks`` inside ``serve.wave``;
the byte counters must equal their closed forms; an untraced engine must
record nothing.  The forest engine's wave carries the tune layer's spans.
"""

import numpy as np
import pytest

from repro import obs
from repro.core import EncodedForest, breadth_first_encode, random_tree
from repro.kernels.tree_eval import ops
from repro.tune import TuneCache

N_ATTRS = 9

# span -> the span it lies directly inside, on the request thread
TREE_PARENT = {
    "serve.batch": "serve.wave",
    "kernel.dispatch": "serve.wave",
    "serve.hooks": "serve.wave",
    "tune.h2d": "kernel.dispatch",
    "tune.resolve": "kernel.dispatch",
    "tune.pad": "kernel.dispatch",
    "tune.variant": "kernel.dispatch",
    "kernel.wait": "kernel.dispatch",
    "kernel.d2h": "kernel.dispatch",
    "kernel.pack": "tune.resolve",
    "kernel.launch": "tune.variant",
}
# siblings in the order the request path runs them
TREE_ORDER = [
    ("serve.wave", ["serve.batch", "kernel.dispatch", "serve.hooks"]),
    ("kernel.dispatch", ["tune.h2d", "tune.resolve", "tune.pad", "tune.variant",
                         "kernel.wait", "kernel.d2h"]),
]


def _tree(seed=0):
    return breadth_first_encode(
        random_tree(n_attrs=N_ATTRS, n_classes=5, max_depth=4, seed=seed))


def _records(m, seed=0):
    return np.random.default_rng(seed).normal(size=(m, N_ATTRS)).astype(np.float32)


def _tree_engine(tmp_path, **kw):
    from repro.serve import TreeServeEngine

    return TreeServeEngine(_tree(), max_batch=4096, cache=TuneCache(tmp_path / "c.json"),
                           engines=("pallas",), retune=None, profile=None, **kw)


def _one_wave(eng, sizes):
    from repro.serve import TreeRequest

    reqs = [TreeRequest(uid=i, records=_records(m, seed=i)) for i, m in enumerate(sizes)]
    eng.run(reqs)
    return reqs


def _spans(tracer):
    """{name: (start, end)} of the spans of one wave (each name once)."""
    out = {}
    for e in tracer.events():
        assert e.name not in out, f"span {e.name!r} recorded twice in one wave"
        out[e.name] = (e.ts_us, e.ts_us + e.dur_us)
    assert len({e.thread for e in tracer.events()}) == 1   # all on the request thread
    return out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_tree_wave_spans_nest_in_phase_order(tmp_path):
    tracer = obs.Tracer()
    eng = _tree_engine(tmp_path, registry=obs.Registry(), tracer=tracer)
    # 120 + 80 records: one wave of 200, padded to its 256-record bucket
    reqs = _one_wave(eng, [120, 80])
    assert all(r.done for r in reqs)
    spans = _spans(tracer)
    assert set(spans) == set(TREE_PARENT) | {"serve.wave"}
    for name, parent in TREE_PARENT.items():
        assert _inside(spans[name], spans[parent]), f"{name} not inside {parent}"
    for parent, kids in TREE_ORDER:
        ends = [spans[k] for k in kids]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:])), f"{parent}: {kids}"


def test_steady_state_wave_has_no_resolve_or_pad(tmp_path):
    tracer = obs.Tracer()
    eng = _tree_engine(tmp_path, tracer=tracer)
    _one_wave(eng, [256])           # a full bucket: nothing to pad
    tracer.clear()
    _one_wave(eng, [256])           # the fast path: nothing to resolve or pack
    spans = _spans(tracer)
    assert "tune.resolve" not in spans and "tune.pad" not in spans
    assert set(spans) == (set(TREE_PARENT) - {"tune.resolve", "tune.pad", "kernel.pack"}
                          | {"serve.wave"})
    # the variant call is the one jitted call and nothing else
    assert [e.name for e in tracer.events()
            if _inside((e.ts_us, e.ts_us + e.dur_us), spans["tune.variant"])
            and e.name != "tune.variant"] == ["kernel.launch"]


def test_transfer_and_padding_counters_have_closed_forms(tmp_path):
    registry = obs.Registry()
    eng = _tree_engine(tmp_path, registry=registry)
    m, bucket_m = 200, 256
    _one_wave(eng, [120, 80])
    cand, _ = eng._eval.resolve(_records(m))
    spec = ops.get_variant(cand.variant)
    packed = ops.PackedTree(eng.tree, N_ATTRS)
    block_m = cand.param_dict.get("block_m") or ops.choose_block_m(
        packed.n_nodes, packed.n_attrs_padded, jump_mode=spec.jump_mode)
    m_pad = -(-bucket_m // block_m) * block_m
    counters = obs.snapshot(registry)["counters"]
    assert counters["tune.h2d_bytes"] == m * N_ATTRS * 4
    # the kernel pads the bucket's rows to whole tiles and A to 128 lanes
    assert counters["kernel.pad_bytes"] == (m_pad * 128 - bucket_m * N_ATTRS) * 4
    assert counters['serve.d2h_bytes{engine="tree"}'] == m * 4
    # the tree's tables were packed once, when the bucket resolved
    assert counters['kernel.packs{level="tree"}'] == 1


@pytest.mark.parametrize("tracer", [None, obs.Tracer(enabled=False)],
                         ids=["null", "disabled"])
def test_untraced_request_path_records_nothing(tmp_path, tracer):
    eng = _tree_engine(tmp_path, tracer=tracer)
    reqs = _one_wave(eng, [120, 80])
    assert all(r.done for r in reqs)
    assert obs.NULL_TRACER.events() == []
    if tracer is not None:
        assert tracer.events() == []


def test_forest_wave_carries_the_tune_spans(tmp_path):
    from repro.serve import ForestServeEngine

    forest = EncodedForest([_tree(seed=s) for s in range(4)])
    tracer, registry = obs.Tracer(), obs.Registry()
    eng = ForestServeEngine(forest, max_batch=256, chunk_records=64, n_classes=5,
                            cache=TuneCache(tmp_path / "c.json"), retune=None,
                            profile=None, registry=registry, tracer=tracer)
    _one_wave(eng, [100, 28])
    by = {}
    for e in tracer.events():
        by.setdefault(e.name, []).append((e.ts_us, e.ts_us + e.dur_us))
    (wave,) = by["serve.wave"]
    for name in ("serve.batch", "serve.hooks"):
        assert _inside(by[name][0], wave)
    assert len(by["tune.h2d"]) == len(by["stream.chunk.submit"]) == 2
    for name in ("tune.h2d", "tune.variant"):
        for s in by[name]:
            assert any(_inside(s, d) for d in by["kernel.dispatch"]), name
    counters = obs.snapshot(registry)["counters"]
    m, t = 128, forest.n_trees
    assert counters["tune.h2d_bytes"] == m * N_ATTRS * 4
    # the chunks' per-tree classes come back, go up again for the vote,
    # and the voted classes come back
    assert counters["stream.d2h_bytes"] == t * m * 4
    assert counters['serve.h2d_bytes{engine="forest"}'] == t * m * 4
    assert counters['serve.d2h_bytes{engine="forest"}'] == m * 4
