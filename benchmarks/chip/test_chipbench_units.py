"""CPU tests of the benchmark's yardstick: traffic, statistics, work, the
reference, the peaks table and the trace arithmetic.  Nothing here loads
the TPU library."""

from __future__ import annotations

import hashlib
import json
import math
import os
import re

import numpy as np
import pytest

from chipbench import harness, reference, tracing, traffic, work

ROOT = harness.find_root()
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def test_open_loop_plan_is_uniform_and_fixed_by_the_rate():
    mix = {"loop": "open", "rate_per_s": 4.0, "pool": 3,
           "records": {"dist": "fixed", "n": 100}}
    p = traffic.plan(mix, 2**31 + 17, 2.5)
    assert p.sizes == (100,) * 3                      # the pool; requests cycle through it
    assert p.due_s == tuple(i / 4.0 for i in range(10))
    assert p.check_requests is None


def test_closed_loop_plan_and_seeded_check_sample():
    mix = {"loop": "closed", "pool": 32, "check_requests": 2,
           "records": {"dist": "log_blocks", "lo": 1, "hi": 1024, "block": 16}}
    p = traffic.plan(mix, 3, 10.0)
    assert p.due_s is None and len(p.sizes) == 32
    assert all(1 <= n <= 1024 for n in p.sizes)
    assert p == traffic.plan(mix, 3, 10.0)
    pos = traffic.check_positions(p, 3, 40)
    assert len(pos) == 2 and pos == traffic.check_positions(p, 3, 40)
    assert traffic.check_positions(p, 3, 1) == [0]


LOG_BLOCKS = {"dist": "log_blocks", "lo": 1, "hi": 1024, "block": 16}
BLOCK = [1, 2, 3, 4, 6, 10, 16, 25, 40, 64, 102, 161, 256, 406, 645, 1024]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_log_blocks_give_every_seed_the_same_sizes_in_every_whole_block(loop):
    assert traffic.block_sizes(LOG_BLOCKS) == BLOCK and sum(BLOCK) == 2765
    mix = {"loop": loop, "rate_per_s": 100.0, "pool": 512, "records": LOG_BLOCKS}
    a = traffic.plan(mix, 2**31 + 1001, 5.0).sizes
    b = traffic.plan(mix, 2**31 + 1002, 5.0).sizes
    n = len(a)        # open: the 500 sends, the last block partial; closed: the pool
    assert n == len(b) == (500 if loop == "open" else 512) and a != b   # order: the seed's
    for i in range(0, n - 15, 16):
        assert sorted(a[i:i + 16]) == sorted(b[i:i + 16]) == BLOCK
    gap = np.abs(np.cumsum(a) - np.cumsum(b))
    assert gap.max() < sum(BLOCK)
    assert gap[np.arange(15, n, 16)].max() == 0           # whole blocks agree exactly


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_pool_of_broken_blocks_is_refused(loop):
    mix = {"loop": loop, "rate_per_s": 100.0, "pool": 40, "records": LOG_BLOCKS}
    with pytest.raises(ValueError, match="not a multiple of block 16"):
        traffic.plan(mix, 7, 1.0)


def test_log_uniform_sizes_are_refused_with_the_reason():
    mix = {"loop": "closed", "pool": 16, "records": {"dist": "log_uniform", "lo": 1,
                                                     "hi": 1024}}
    with pytest.raises(ValueError, match="depend on the seed.*'log_blocks'"):
        traffic.plan(mix, 7, 1.0)


def test_warm_up_sizes_hold_every_size_in_whole_blocks_largest_first():
    mix = {"loop": "open", "rate_per_s": 100.0, "pool": 512, "records": LOG_BLOCKS}
    w = traffic.warmup_sizes(mix, 2**31 + 5, 256)
    assert w[0] == 1024 and sorted(w[:16]) == BLOCK
    assert all(sorted(w[i:i + 16]) == BLOCK for i in range(0, 256, 16))
    fixed = dict(mix, records={"dist": "fixed", "n": 300})
    assert traffic.warmup_sizes(fixed, 5, 4) == [300] * 4


def test_warm_up_serves_one_whole_block_before_it_settles():
    class Quiet:
        """An engine that compiles nothing and keeps the sizes it served."""

        engine = None

        def __init__(self):
            self.served = []

        def serve(self, uid, records):
            self.served.append(records.shape[0])

        def counter(self, prefix):
            return 0

        def drain(self):
            pass

    class Zeros:
        def draw(self, gen, n):
            return np.zeros((n, 2), np.float32)

    def cell(spec):
        mix = {"loop": "open", "rate_per_s": 1.0, "pool": 16, "records": spec}
        return harness.Cell("c", 1, {}, mix, (), ())

    a = Quiet()
    harness.warm_up(a, Zeros(), cell(LOG_BLOCKS), 11, harness.Compiles())
    assert sorted(a.served) == BLOCK                      # nothing compiles: one block
    b = Quiet()
    harness.warm_up(b, Zeros(), cell({"dist": "fixed", "n": 8}), 11, harness.Compiles())
    assert b.served == [8] * (1 + harness.SETTLE_WAVES)


def test_the_stream_plan_and_records_are_those_pinned_before_log_blocks():
    """seg_cart.stream's sizes, due times, warm-up sizes and first request,
    hashed: fixed sizes draw exactly what they drew before ``log_blocks``."""
    cell = harness.load_cell(ROOT, "seg_cart.stream")
    seed = 2**31 + 4242
    p = traffic.plan(cell.mix, seed, 30.0)
    src = harness.load_part("generators", "segmentation_twin").Source(cell.config)
    first = src.draw(traffic.rng(seed, traffic.WINDOW, 0), p.sizes[0])
    h = hashlib.sha256()
    h.update(np.asarray(p.sizes, np.int64).tobytes())
    h.update(np.asarray(p.due_s, np.float64).tobytes())
    h.update(np.asarray(traffic.warmup_sizes(cell.mix, seed, harness.MAX_WARMUP_WAVES),
                        np.int64).tobytes())
    h.update(np.ascontiguousarray(first).tobytes())
    assert (len(p.sizes), len(p.due_s), first.shape) == (64, 1920, (65536, 19))
    assert h.hexdigest() == \
        "31164306920e36303c287d9720cd806cdf631ee71df8a8ce474ecb3de3a41d22"


def test_the_same_seed_gives_the_same_records():
    cfg = json.load(open(os.path.join(harness.BENCH_DIR, "configs", "seg_cart.json")))
    src = harness.load_part("generators", "segmentation_twin").Source(cfg)
    big = 2**31 + 12345
    a = src.draw(traffic.rng(big, traffic.WINDOW, 3), 257)
    b = src.draw(traffic.rng(big, traffic.WINDOW, 3), 257)
    c = src.draw(traffic.rng(big, traffic.WINDOW, 4), 257)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.shape == (257, 19) and a.dtype == np.float32


def test_segmentation_twin_is_the_papers_table():
    from repro.data.segmentation import make_segmentation

    cfg = json.load(open(os.path.join(harness.BENCH_DIR, "configs", "seg_cart.json")))
    src = harness.load_part("generators", "segmentation_twin").Source(cfg)
    d = make_segmentation(seed=cfg["records"]["table_seed"])
    assert np.array_equal(src.table, np.concatenate([d.x_train, d.x_test]))
    frame = src.draw(traffic.rng(9, traffic.WINDOW, 0), 10_000)
    assert frame.shape == (10_000, 19)
    # permutation tiling: every table row appears before any repeats
    rows = {r.tobytes() for r in frame[: src.table.shape[0]]}
    assert len(rows) == src.table.shape[0]


# ---------------------------------------------------------------------------
# statistics over all requests
# ---------------------------------------------------------------------------


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 50) == 50
    assert harness.percentile(vals, 95) == 95
    assert harness.percentile([7.0], 95) == 7.0
    assert harness.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_latency_counts_from_due_time_in_an_open_loop():
    # the second request fell due at 1.0 but the server only sent it at 1.5
    served = [harness.Served(0, 10, due=0.0, sent=0.0, done=0.6, out=np.zeros(10)),
              harness.Served(1, 10, due=0.5, sent=0.6, done=1.2, out=np.zeros(10)),
              harness.Served(2, 10, due=1.0, sent=1.2, done=1.8, out=np.zeros(10)),
              harness.Served(3, 10, due=2.0, sent=2.001, done=2.5, out=np.zeros(10))]
    m = harness.e2e_metrics(served, 0.0, 12.0)
    lat = sorted([600.0, 700.0, 800.0, 500.0])
    assert m["latency_p50_ms"] == pytest.approx(lat[1])
    assert m["latency_p95_ms"] == pytest.approx(lat[3])
    assert m["records_per_s"] == pytest.approx(40 / 2.5)
    assert m["setup_s"] == 12.0
    # only sends that found the server idle measure the generator
    assert harness.generator_lateness_ms(served) == pytest.approx([0.0, 1.0])


def test_latency_counts_from_send_time_in_a_closed_loop_and_failures_count():
    served = [harness.Served(0, 5, None, sent=1.0, done=1.25, out=np.zeros(5)),
              harness.Served(1, 5, None, sent=1.25, done=1.75, out=np.zeros(5)),
              harness.Served(2, 5, None, sent=1.75, done=1.8, error="boom")]
    m = harness.e2e_metrics(served, 1.0, 0.0)
    assert m["latency_p50_ms"] == pytest.approx(250.0)
    assert m["latency_p95_ms"] == pytest.approx(500.0)
    assert m["records_per_s"] == pytest.approx(10 / 0.75)


def test_answers_are_copied_into_rows_made_before_the_window():
    store = harness.Answers(2, 8)
    a = np.arange(5, dtype=np.int32)
    kept = store.keep(a)
    assert np.array_equal(kept, a) and not np.shares_memory(kept, a)
    assert np.shares_memory(kept, store.blocks[0])
    b = store.keep(np.arange(8, dtype=np.int32))
    c = store.keep(np.arange(3, dtype=np.int32))        # past the estimate: a new block
    assert len(store.blocks) == 2 and np.array_equal(c, [0, 1, 2])
    assert np.array_equal(kept, a) and np.array_equal(b, np.arange(8))
    # what is not a row of int32 classes is kept as it came, for the check to judge
    odd = np.arange(4, dtype=np.int64)
    assert store.keep(odd) is odd
    wide = np.zeros(9, np.int32)
    assert store.keep(wide) is wide
    plan = traffic.plan({"loop": "open", "rate_per_s": 4.0, "pool": 2,
                         "records": {"dist": "fixed", "n": 100}}, 1, 2.5)
    assert harness.answer_store(plan).blocks[0].shape == (10, 100)


# ---------------------------------------------------------------------------
# work and peaks
# ---------------------------------------------------------------------------


def test_work_count():
    w = work.wave_work(1000, 4000.0, n_attrs=19, tree_nodes=(31,))
    assert w.ops == 4000.0                                   # no vote for one tree
    assert w.bytes == 1000 * 19 * 4 + 31 * 16 + 1000 * 4
    f = work.wave_work(1000, 8000.0, n_attrs=54, tree_nodes=(511,) * 2)
    assert f.ops == 8000.0 + 2000.0                           # one vote per tree and record
    assert f.bytes == 1000 * 54 * 4 + 2 * 511 * 16 + 1000 * 4
    p = work.peaks("TPU v5 lite")
    assert p.op_per_s == 393e12 and p.hbm_byte_per_s == 819e9
    assert w.least_s(p) == pytest.approx(w.bytes / 819e9)
    assert w.bound(p) == "bytes"


def test_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _walk(nodes, record):
    """Procedure 2 by hand, one record at a time: the test's own oracle."""
    i = 0
    while nodes[i][1] is not None:
        a, t, c, _ = nodes[i]
        i = c + 1 if np.float32(record[a]) > np.float32(t) else c
    return nodes[i][3], None


def test_reference_matches_a_walk_over_the_stored_node_list():
    cfg = json.load(open(os.path.join(harness.BENCH_DIR, "configs", "seg_cart.json")))
    nodes = cfg["model"]["trees"][0]
    model = harness.load_part("generators", "stored_tree").build(cfg, 0)
    assert model.n_nodes == (31,) and model.depth == 11
    src = harness.load_part("generators", "segmentation_twin").Source(cfg)
    recs = src.draw(traffic.rng(4, traffic.WINDOW, 0), 3000)
    recs[:5] = [np.nan] * 19
    got, comps = reference.classify(model, recs)
    want = [_walk(nodes, r)[0] for r in recs]
    assert np.array_equal(got, want)
    assert np.all((comps >= 1) & (comps <= 11))


def test_forest_vote_ties_go_to_the_lowest_class():
    per_tree = np.array([[2, 1, 0], [1, 1, 3], [0, 5, 3], [0, 5, 6]])
    assert reference.vote(per_tree, 7).tolist() == [0, 1, 3]


def test_stored_tree_has_the_papers_shape_and_is_the_one_seg_tree_makes(capsys):
    from repro.configs.paper_segmentation import CONFIG

    cfg = json.load(open(os.path.join(harness.BENCH_DIR, "configs", "seg_cart.json")))
    model = harness.load_part("generators", "stored_tree").build(cfg, 0)
    leaves = int(np.count_nonzero(model.cls >= 0))
    assert (model.n_nodes[0], leaves, model.depth) == (
        CONFIG.tree_nodes, CONFIG.tree_leaves, CONFIG.tree_depth)
    assert (cfg["model"]["n_nodes"], cfg["model"]["n_leaves"], cfg["model"]["depth"]) == (
        CONFIG.tree_nodes, CONFIG.tree_leaves, CONFIG.tree_depth)
    assert harness.load_part("tools", "seg_tree").main() == 0
    made = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert made["twin_seed"] == cfg["records"]["table_seed"]
    assert made["trees"] == cfg["model"]["trees"]


def test_bf16_control_differs_from_the_reference():
    cfg = json.load(open(os.path.join(harness.BENCH_DIR, "configs", "seg_cart.json")))
    model = harness.load_part("generators", "stored_tree").build(cfg, 0)
    src = harness.load_part("generators", "segmentation_twin").Source(cfg)
    recs = src.draw(traffic.rng(1, traffic.WINDOW, 0), 65536)
    f32, _ = reference.classify(model, recs)
    bf16, _ = reference.classify(model, recs, dtype=reference.BF16)
    assert np.count_nonzero(f32 != bf16) > 0


# ---------------------------------------------------------------------------
# trace arithmetic on a hand-made trace
# ---------------------------------------------------------------------------

MS = 1e6  # ns
KERNEL = ('%_tree_eval_padded.{n} = s32[1,65536,1]{{2,1,0:T(8,128)}} custom-call(f32[65536,128] '
          '%records.1), custom_call_target="tpu_custom_call"')


def _trace():
    th = "python"
    return {
        "host_spans": [
            [th, "bench.window", 0, 100 * MS],
            [th, "bench.wait", 0, 10 * MS],
            [th, "bench.request", 10 * MS, 42 * MS],    # 10..52
            [th, "serve.wave", 10 * MS, 40 * MS],       # 10..50
            [th, "stream.eval", 15 * MS, 30 * MS],      # 15..45
            [th, "kernel.dispatch", 20 * MS, 10 * MS],  # 20..30
            [th, "bench.request", 58 * MS, 32 * MS],    # 58..90
            [th, "serve.wave", 60 * MS, 30 * MS],       # 60..90
            [th, "kernel.dispatch", 65 * MS, 20 * MS],  # 65..85
            ["retune:x", "serve.retune.measure", 0, 100 * MS],
        ],
        "device_ops": [
            [0, "%fusion.1 = f32[8] fusion(f32[8] %x)", 22 * MS, 4 * MS],
            [0, KERNEL.format(n=1), 25 * MS, 5 * MS],               # overlaps fusion.1
            [0, KERNEL.format(n=7), 70 * MS, 10 * MS],
            [0, "%copy.3 = f32[8] copy(f32[8] %y)", 95 * MS, 10 * MS],  # runs past the window
        ],
    }


def test_reduction_of_a_hand_made_trace():
    red = tracing.Reduced(_trace())
    assert red.window_s == pytest.approx(0.1)
    assert len(red.requests) == 2
    # busy: 22..30, 70..80, 95..100 (clipped) = 23 ms
    assert red.busy_s() == pytest.approx(0.023)
    assert red.kernel_s() == pytest.approx(0.015)
    # serve self time: request 1: 42 - 30 (stream.eval covers kernel.dispatch) = 12;
    # request 2: 32 - 20 = 12  -> 12 ms per request
    assert red.self_ms_per_request(("bench.request",), ("stream.", "kernel.dispatch")) == \
        pytest.approx(12.0)
    # stream self time: request 1: 30 - 10 = 20; request 2 has none -> 20 / 2
    assert red.self_ms_per_request(("stream.",), ("kernel.dispatch",)) == pytest.approx(10.0)
    gaps = dict(red.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.023)
    assert gaps["bench.wait"] == pytest.approx(0.010)
    assert gaps["kernel.dispatch"] == pytest.approx(0.002 + 0.005 + 0.005)
    assert gaps["bench.request"] == pytest.approx(0.002 + 0.002)
    ops = dict(red.device_ops())
    assert ops["_tree_eval_padded"] == pytest.approx(0.015)
    assert ops["copy"] == pytest.approx(0.005) and ops["fusion"] == pytest.approx(0.004)


def test_per_layer_readers_on_a_hand_made_trace():
    red = tracing.Reduced(_trace())
    ctx = harness.TraceContext(red, least_s=0.0015, peaks=work.peaks("TPU v5 lite"))
    read = {m: harness.load_part("metrics", m).read(ctx) for m in
            ("serve_host_ms", "kernel_ms", "kernel_roofline", "device_ms", "wave_mfu")}
    assert read["serve_host_ms"] == pytest.approx(12.0)
    assert read["device_ms"] == pytest.approx((8 + 10) / 2)
    assert read["kernel_ms"] == pytest.approx(7.5)
    assert read["kernel_roofline"] == pytest.approx(10.0)
    assert read["wave_mfu"] == pytest.approx(100 * 0.0015 / 0.023)   # over the busy time
    empty = dict(_trace(), device_ops=[])
    ctx0 = harness.TraceContext(tracing.Reduced(empty), 0.0015, ctx.peaks)
    # nothing to read: no value, never a 0 share
    assert harness.load_part("metrics", "kernel_roofline").read(ctx0) is None
    assert harness.load_part("metrics", "kernel_ms").read(ctx0) is None


def test_interval_helpers():
    assert tracing.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tracing.gaps([(1, 2), (4, 9)], 0, 10) == [(0, 1), (2, 4), (9, 10)]
    assert tracing.overlap([(0, 10)], [(2, 3), (8, 12)]) == 3


# ---------------------------------------------------------------------------
# BENCHMARK.json finds every part by name
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files_by_name():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        cfg = cell.config
        for part in ("model", "records"):
            assert os.path.isfile(os.path.join(harness.BENCH_DIR, "generators",
                                               cfg[part]["generator"] + ".py"))
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "engines",
                                           cfg["engine"]["kind"] + ".py"))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"].split(".")[0] + ".py"))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


# ---------------------------------------------------------------------------
# the reduction on a small trace recorded on a TPU v5e (seg_cart.stream, 3 waves)
# ---------------------------------------------------------------------------


def test_reduction_of_a_recorded_trace():
    path = os.path.join(harness.BENCH_DIR, "testdata", "seg_cart_stream_3waves.json")
    raw = json.load(open(path))
    red = tracing.Reduced(raw)
    assert len(red.requests) == 3
    kernels = [o for o in raw["device_ops"] if tracing.is_kernel(o[1])]
    assert len(kernels) == 3
    assert red.kernel_s() == pytest.approx(sum(o[3] for o in kernels) * 1e-9)
    assert all(tracing.op_name(o[1]) == "_tree_eval_padded" for o in kernels)
    # host spans and device ops share one clock: every kernel ran inside a dispatch
    dispatch = [(s, s + d) for _, n, s, d in raw["host_spans"] if n == "kernel.dispatch"]
    assert all(any(a <= o[2] and o[2] + o[3] <= b for a, b in dispatch) for o in kernels)
    busy = red.busy_s()
    assert 0 < red.kernel_s() <= busy < red.window_s
    assert sum(v for _, v in red.idle_gaps()) == pytest.approx(red.window_s - busy)
    assert sum(v for _, v in red.device_ops()) >= busy
    ms = red.self_ms_per_request(("bench.request",), ("stream.", "kernel.dispatch"))
    assert 0 < ms < 5
    assert red.self_ms_per_request(("stream.",), ("kernel.dispatch",)) is None
    waves = [e - s for s, e in red.requests]
    assert 0 < red.busy_ms_per_request() <= max(waves) * 1e-6
