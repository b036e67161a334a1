"""Perf observability: the bench trajectory store, the noise-aware
regression detector (and its CLI gate), kernel cost/roofline accounting on
the tuner's measurement path, cascade host-compaction metrics, and the SLO
flight recorder's debug bundles.
"""

import importlib.util
import json
import pathlib
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import breadth_first_encode, paper_tree, random_tree
from repro.core.forest import EncodedForest, eval_forest_cascade
from repro.obs.perf import (
    ENV_KEYS,
    append_history,
    baseline_pool,
    detect_regressions,
    env_key,
    extract_series,
    load_history,
    skipped_series,
)
from repro.serve import TreeRequest, TreeServeEngine
from repro.tune import TuneCache

REPO = pathlib.Path(__file__).resolve().parent.parent

ENV = {
    "backend": "cpu",
    "device_kind": "cpu",
    "device_count": 1,
    "pallas_interpret": "true",
    "jax": "0.4.37",
}


def _run(medians, env=ENV):
    """One trajectory record with the given {series: median_ms}."""
    return {
        "bench": "t",
        "ts": "2026-01-01T00:00:00+00:00",
        "source": "test",
        "env": dict(env),
        "series": {k: {"median_ms": float(v)} for k, v in medians.items()},
    }


def _records(m, a, seed=0):
    return np.random.default_rng(seed).normal(size=(m, a)).astype(np.float32)


# ---------------------------------------------------------------------------
# regression detector
# ---------------------------------------------------------------------------


class TestRegressionDetector:
    def test_single_run_history_never_flags(self):
        assert detect_regressions([_run({"w": 1.0})]) == []
        assert detect_regressions([]) == []

    def test_env_mismatch_never_compares(self):
        # 10x slower on a different backend is a different experiment, not a
        # regression — the baseline pool must come up empty.
        hist = [_run({"w": 1.0}) for _ in range(4)]
        tpu = dict(ENV, backend="tpu", device_kind="TPU v5e")
        hist.append(_run({"w": 10.0}, env=tpu))
        assert baseline_pool(hist) == []
        assert detect_regressions(hist) == []
        # same-env latest still compares against same-env predecessors only
        hist.append(_run({"w": 10.0}))
        pool = baseline_pool(hist)
        assert len(pool) == 4 and all(env_key(r["env"]) == env_key(ENV) for r in pool)
        flagged = detect_regressions(hist)
        assert [r.series for r in flagged] == ["w"]

    def test_mad_zero_identical_history(self):
        # All-identical history: MAD = 0, so the relative floor carries the
        # gate alone — an equal latest passes, sub-threshold jitter passes,
        # a 2x latest is flagged.
        hist = [_run({"w": 1.0}) for _ in range(5)]
        assert detect_regressions(hist + [_run({"w": 1.0})]) == []
        assert detect_regressions(hist + [_run({"w": 1.4})]) == []
        flagged = detect_regressions(hist + [_run({"w": 2.0})])
        assert len(flagged) == 1
        r = flagged[0]
        assert r.series == "w" and r.mad_ms == 0.0
        assert r.baseline_ms == pytest.approx(1.0)
        assert r.ratio == pytest.approx(2.0)
        assert r.threshold_ms == pytest.approx(1.5)
        assert "x2.00" in r.describe()

    def test_mad_widens_gate_on_noisy_series(self):
        # baseline median 12, MAD 2: k_mad*MAD = 10 beats the relative floor
        # (6), so a 20 ms latest — over 1.5x baseline — still passes.
        hist = [_run({"w": v}) for v in (10.0, 14.0, 10.0, 14.0, 12.0)]
        assert detect_regressions(hist + [_run({"w": 20.0})]) == []
        flagged = detect_regressions(hist + [_run({"w": 23.0})])
        assert [r.series for r in flagged] == ["w"]

    def test_synthetic_2x_regression_flagged(self):
        hist = [_run({"fast": 1.0, "slow": 8.0}) for _ in range(5)]
        flagged = detect_regressions(hist + [_run({"fast": 2.0, "slow": 8.0})])
        assert [(r.series, round(r.ratio, 2)) for r in flagged] == [("fast", 2.0)]

    def test_new_series_is_not_a_regression(self):
        hist = [_run({"w": 1.0}) for _ in range(3)]
        assert detect_regressions(hist + [_run({"w": 1.0, "brand_new": 99.0})]) == []

    def test_window_bounds_the_pool(self):
        hist = [_run({"w": float(i)}) for i in range(10)]
        pool = baseline_pool(hist, window=3)
        assert [r["series"]["w"]["median_ms"] for r in pool] == [6.0, 7.0, 8.0]


class TestSkippedSeries:
    """Series detect_regressions silently skips must still be reportable."""

    def test_thin_baseline_is_reported_with_its_count(self):
        # seed run only: the series has zero same-env predecessors
        assert skipped_series([_run({"w": 1.0})]) == [("w", 0)]
        # one predecessor: still below the default min_runs=2
        hist = [_run({"w": 1.0}), _run({"w": 1.0, "new": 5.0})]
        assert skipped_series(hist) == [("new", 0), ("w", 1)]
        # enough history: nothing to report
        assert skipped_series([_run({"w": 1.0}) for _ in range(3)]) == []
        assert skipped_series([]) == []

    def test_env_change_orphans_the_baseline(self):
        # same trick as test_env_mismatch_never_compares: a backend switch
        # empties the pool, so every series of the latest run shows up skipped
        tpu = dict(ENV, backend="tpu", device_kind="TPU v5e")
        hist = [_run({"w": 1.0}) for _ in range(4)] + [_run({"w": 1.0}, env=tpu)]
        assert skipped_series(hist) == [("w", 0)]

    def test_min_runs_raises_the_bar(self):
        hist = [_run({"w": 1.0}) for _ in range(4)]
        assert skipped_series(hist, min_runs=3) == []
        assert skipped_series(hist, min_runs=4) == [("w", 3)]


# ---------------------------------------------------------------------------
# history store
# ---------------------------------------------------------------------------


class TestHistoryStore:
    def test_extract_series_names_and_fallbacks(self):
        payload = {
            "entries": [
                {"name": "w", "median_ms": 1.5, "mad_ms": 0.1},
                {"workload": "x", "tuned_ms": 2.0, "tuned_mad_ms": 0.2,
                 "variant": "fused"},
                {"name": "acc_only", "accuracy": 0.9},  # no median -> skipped
                {"name": "w", "median_ms": 9.0},        # collision -> suffixed
            ],
            "forest_entries": [
                {"name": "f", "forest_tuned_ms": 3.0, "stages": 2, "bound": 0.25},
            ],
        }
        series = extract_series(payload)
        assert series["w"] == {"median_ms": 1.5, "mad_ms": 0.1}
        assert series["x/fused"] == {"median_ms": 2.0, "mad_ms": 0.2}
        assert series["w#2"] == {"median_ms": 9.0}
        assert series["f/s2/b0.25"] == {"median_ms": 3.0}
        assert "acc_only" not in series

    def test_append_load_roundtrip(self, tmp_path):
        payload = {"env": dict(ENV),
                   "entries": [{"name": "w", "median_ms": 1.0, "mad_ms": 0.05}]}
        append_history(tmp_path, "toy", payload, ts="2026-01-01T00:00:00+00:00")
        append_history(tmp_path, "toy", payload)
        records = load_history(tmp_path / "toy.jsonl")
        assert len(records) == 2
        assert records[0]["ts"] == "2026-01-01T00:00:00+00:00"
        assert records[0]["series"]["w"]["median_ms"] == 1.0
        assert env_key(records[0]["env"]) == env_key(ENV)
        assert all(k in records[0]["env"] for k in ENV_KEYS)

    def test_corrupt_line_raises_with_location(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text('{"ok": 1}\nnot json at all\n')
        with pytest.raises(ValueError, match="bad.jsonl:2"):
            load_history(path)

    def test_write_bench_json_appends_history(self, tmp_path, monkeypatch):
        # the benches' own writer must leave a trajectory line behind
        monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
        monkeypatch.syspath_prepend(str(REPO))
        from benchmarks.common import write_bench_json

        entries = [{"name": "w", "median_ms": 1.25, "mad_ms": 0.01}]
        write_bench_json("toybench", entries)
        write_bench_json("toybench", entries)
        records = load_history(tmp_path / "history" / "toybench.jsonl")
        assert len(records) == 2
        assert records[-1]["source"] == "bench"
        assert records[-1]["series"]["w"] == {"median_ms": 1.25, "mad_ms": 0.01}
        assert records[-1]["env"].get("backend")  # real env header attached


# ---------------------------------------------------------------------------
# check_regressions.py CLI (the CI perf gate)
# ---------------------------------------------------------------------------


def _cli():
    spec = importlib.util.spec_from_file_location(
        "check_regressions", REPO / "results" / "check_regressions.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCheckRegressionsCLI:
    def _write(self, d, runs):
        d.mkdir(parents=True, exist_ok=True)
        with open(d / "toy.jsonl", "w") as f:
            for r in runs:
                f.write(json.dumps(r, sort_keys=True) + "\n")

    def test_injected_2x_slowdown_exits_nonzero(self, tmp_path, capsys):
        self._write(tmp_path, [_run({"w": 1.0}) for _ in range(4)]
                    + [_run({"w": 2.0})])
        rc = _cli().main(["--history-dir", str(tmp_path)])
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_clean_history_exits_zero(self, tmp_path):
        self._write(tmp_path, [_run({"w": 1.0}) for _ in range(5)])
        assert _cli().main(["--history-dir", str(tmp_path), "--strict"]) == 0

    def test_committed_history_is_clean(self, capsys):
        # the repo's own trajectory must pass the exact gate CI runs
        assert _cli().main(["--strict"]) == 0
        out = capsys.readouterr().out
        assert "0 regression(s)" in out

    def test_skipped_series_reported_not_failed(self, tmp_path, capsys):
        # one predecessor for "w", none for "fresh": both below min_runs=2,
        # so the gate reports them without failing — even under --strict
        self._write(tmp_path, [_run({"w": 1.0}), _run({"w": 1.0, "fresh": 2.0})])
        rc = _cli().main(["--history-dir", str(tmp_path), "--strict"])
        out = capsys.readouterr().out
        assert rc == 0
        assert ("SKIPPED  toy/fresh: insufficient history "
                "(0 same-env run(s), need 2)") in out
        assert ("SKIPPED  toy/w: insufficient history "
                "(1 same-env run(s), need 2)") in out
        assert "2 skipped" in out

    def test_skipped_series_in_json_and_min_runs(self, tmp_path, capsys):
        self._write(tmp_path, [_run({"w": 1.0}) for _ in range(3)])
        rc = _cli().main(["--history-dir", str(tmp_path), "--json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0 and data["skipped"] == []
        # raising the bar makes the same history insufficient
        rc = _cli().main(["--history-dir", str(tmp_path), "--json",
                          "--min-runs", "5"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["skipped"] == [
            {"bench": "toy", "series": "w", "n_baseline": 2}]

    def test_empty_history_file_is_a_problem_not_a_crash(self, tmp_path, capsys):
        (tmp_path / "toy.jsonl").write_text("")
        rc = _cli().main(["--history-dir", str(tmp_path), "--json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0                      # lax mode: warn only
        assert data["checked"] == 0 and data["regressions"] == []
        assert any("toy" in p for p in data["problems"])
        assert _cli().main(["--history-dir", str(tmp_path), "--strict"]) == 1

    def test_strict_fails_on_missing_or_corrupt(self, tmp_path):
        cli = _cli()
        empty = tmp_path / "empty"
        empty.mkdir()
        assert cli.main(["--history-dir", str(empty)]) == 0  # lax: warn only
        assert cli.main(["--history-dir", str(empty), "--strict"]) == 1
        bad = tmp_path / "bad"
        bad.mkdir()
        (bad / "toy.jsonl").write_text("garbage\n")
        assert cli.main(["--history-dir", str(bad), "--strict"]) == 1
        missing = ["--history-dir", str(tmp_path), "--bench", "nope", "--strict"]
        assert cli.main(missing) == 1


# ---------------------------------------------------------------------------
# candidate cost / roofline accounting
# ---------------------------------------------------------------------------


class TestCandidateCost:
    def test_roofline_fraction_math(self):
        from repro.launch.roofline import DEVICE_PEAKS
        from repro.tune.measure import roofline_fraction

        kind = "TPU v5 lite"
        peaks = DEVICE_PEAKS[kind]
        # memory-bound: floor = bytes/BW; 1 s of HBM traffic in 2 s -> 0.5
        assert roofline_fraction(0.0, peaks.hbm_bw, 2000.0, kind) == pytest.approx(0.5)
        # compute-bound: floor = flops/peak
        assert roofline_fraction(peaks.bf16_flops, 0.0, 1000.0, kind) == pytest.approx(1.0)
        assert roofline_fraction(1.0, 1.0, 0.0, kind) == 0.0
        assert roofline_fraction(1.0, 1.0, float("inf"), kind) == 0.0
        # a device outside the table has no roofline — never v5e numbers
        assert roofline_fraction(1.0, 1.0, 1.0, "cpu") is None
        assert roofline_fraction(1.0, 1.0, 1.0) is None   # this host's CPU

    def test_measure_candidate_carries_cost(self):
        import jax.numpy as jnp

        from repro.tune.measure import bucket_pad_records, measure_candidate
        from repro.tune.space import WorkloadShape, search_space

        enc = breadth_first_encode(paper_tree())
        rec = jnp.asarray(_records(64, 19))
        shape = WorkloadShape.of(rec, enc)
        rec = bucket_pad_records(rec, shape.bucket().m)
        cand = next(iter(search_space(shape)))
        m = measure_candidate(cand, rec, enc, max_depth=shape.depth,
                              warmup=1, iters=2)
        assert not m.failed
        assert m.cost is not None
        # tree kernels are compare/gather programs: bytes carry the signal,
        # dot/conv FLOPs are ~0 — assert the memory side, not the flop side
        assert m.cost["bytes"] > 0
        assert m.cost["flops"] >= 0
        # the CPU is not in the peaks table: no roofline share is claimed
        assert "roofline_frac" not in m.cost
        assert m.mad_ms >= 0.0

    def test_tune_workload_publishes_cost_gauges(self, tmp_path):
        from repro.tune import tune_workload

        enc = breadth_first_encode(paper_tree())
        r = obs.Registry()
        entry, ms = tune_workload(_records(64, 19), enc,
                                  cache=TuneCache(tmp_path / "c.json"),
                                  warmup=0, iters=1, registry=r)
        assert any(m.cost is not None for m in ms if not m.failed)
        snap = obs.snapshot(r)
        byte_series = {k: v for k, v in snap["gauges"].items()
                       if k.startswith("tune.candidate_bytes")}
        roof_series = [k for k in snap["gauges"] if k.startswith("tune.roofline_frac")]
        assert byte_series and not roof_series   # no CPU roofline published
        assert any(v > 0 for v in byte_series.values())
        assert any(f'variant="{entry.variant}"' in k for k in byte_series)


# ---------------------------------------------------------------------------
# cascade host-compaction instrumentation
# ---------------------------------------------------------------------------


class TestCascadeCompaction:
    def test_registry_and_tracer_thread_through(self):
        trees = [breadth_first_encode(random_tree(n_attrs=9, n_classes=6,
                                                  max_depth=2 + (i % 4), seed=i))
                 for i in range(8)]
        forest = EncodedForest(trees)
        rec = _records(256, 9)
        r, t = obs.Registry(), obs.Tracer()
        res = eval_forest_cascade(forest, rec, n_classes=6, stages=3,
                                  bound=1.0, registry=r, tracer=t)
        assert np.asarray(res.classes).shape == (256,)
        snap = obs.snapshot(r)
        compact = {k: v for k, v in snap["histograms"].items()
                   if k.startswith("cascade.compact_ms")}
        assert compact, "cascade.compact_ms never observed"
        assert sum(v["count"] for v in compact.values()) >= 1
        spans = [ev for ev in t.chrome_trace()["traceEvents"]
                 if ev.get("name") == "cascade.compact"]
        phases = {ev.get("args", {}).get("phase") for ev in spans}
        assert {"gather", "scatter"} <= phases


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_breach_and_manual_dump(self, tmp_path):
        r = obs.Registry()
        pol = obs.FlightPolicy(slo_ms=5.0, capacity=4, out_dir=str(tmp_path),
                               min_dump_interval_s=0.0, dump_on_breach=False)
        fr = obs.FlightRecorder(pol, registry=r, engine="unit")
        assert fr.note_wave(latency_ms=1.0, bucket="b") is False
        for i in range(6):
            assert fr.note_wave(latency_ms=10.0 + i, records=8) is True
        waves = fr.waves()
        assert len(waves) == 4  # ring bounded by capacity
        assert all(w["breach"] for w in waves)
        snap = obs.snapshot(r)
        assert snap["counters"]['flight.slo_breaches{engine="unit"}'] == 6
        out = fr.dump("manual")
        bundle = json.loads((out / "flight.json").read_text())
        assert bundle["reason"] == "manual" and len(bundle["waves"]) == 4
        assert bundle["policy"]["slo_ms"] == 5.0
        assert bundle["metrics"]["counters"]['flight.slo_breaches{engine="unit"}'] == 6

    def test_no_slo_means_no_breach(self, tmp_path):
        fr = obs.FlightRecorder(obs.FlightPolicy(out_dir=str(tmp_path)))
        assert fr.note_wave(latency_ms=1e9) is False
        assert not list(tmp_path.glob("flight-*"))

    def test_exception_dumps_bundle(self, tmp_path):
        fr = obs.FlightRecorder(
            obs.FlightPolicy(out_dir=str(tmp_path), min_dump_interval_s=0.0),
            engine="unit")
        fr.note_exception(ValueError("boom"))
        bundles = list(tmp_path.glob("flight-unit-*-exception"))
        assert len(bundles) == 1
        bundle = json.loads((bundles[0] / "flight.json").read_text())
        assert bundle["waves"][-1]["exception"] == "ValueError"
        assert bundle["waves"][-1]["message"] == "boom"

    def test_dump_rate_limit(self, tmp_path):
        fr = obs.FlightRecorder(
            obs.FlightPolicy(slo_ms=0.001, out_dir=str(tmp_path),
                             min_dump_interval_s=3600.0),
            engine="unit")
        for _ in range(5):
            fr.note_wave(latency_ms=100.0)
        assert len(list(tmp_path.glob("flight-unit-*"))) == 1

    def test_rate_limit_survives_simultaneous_breaches(self, tmp_path):
        # two request threads breach at once: both breaches count, but the
        # window admits exactly one bundle — no dir collision, no double dump
        r = obs.Registry()
        fr = obs.FlightRecorder(
            obs.FlightPolicy(slo_ms=0.001, out_dir=str(tmp_path),
                             min_dump_interval_s=3600.0),
            registry=r, engine="unit")
        barrier = threading.Barrier(2)
        breached = []

        def breach():
            barrier.wait()
            breached.append(fr.note_wave(latency_ms=100.0, bucket="b"))

        ts = [threading.Thread(target=breach) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert breached == [True, True]
        bundles = list(tmp_path.glob("flight-unit-*"))
        assert len(bundles) == 1
        json.loads((bundles[0] / "flight.json").read_text())  # intact bundle
        snap = obs.snapshot(r)
        assert snap["counters"]['flight.slo_breaches{engine="unit"}'] == 2
        dumps = {k: v for k, v in snap["counters"].items()
                 if k.startswith("flight.dumps")}
        assert sum(dumps.values()) == 1
        # both waves still made the ring, dumped or not
        assert sum(1 for w in fr.waves() if w.get("breach")) == 2

    def test_drift_rides_the_ring_without_dumping(self, tmp_path):
        fr = obs.FlightRecorder(
            obs.FlightPolicy(out_dir=str(tmp_path), min_dump_interval_s=0.0),
            engine="unit")
        fr.note_drift(bucket="b", distance=0.42, engine="tree")
        assert not list(tmp_path.glob("flight-*"))      # context, not a dump
        w = fr.waves()[-1]
        assert w["drift"] is True and w["distance"] == 0.42
        assert w["bucket"] == "b" and w["engine"] == "tree"

    def test_serve_engine_slo_breach_produces_loadable_bundle(self, tmp_path):
        # the acceptance path: an unmeetable SLO on a real serve engine must
        # count breaches and drop a bundle whose Perfetto trace parses
        enc = breadth_first_encode(paper_tree())
        r, t = obs.Registry(), obs.Tracer()
        pol = obs.FlightPolicy(slo_ms=1e-6, out_dir=str(tmp_path / "fl"),
                               min_dump_interval_s=0.0)
        eng = TreeServeEngine(enc, max_batch=64,
                              cache=TuneCache(tmp_path / "c.json"),
                              retune=None, registry=r, tracer=t, flight=pol)
        reqs = [TreeRequest(uid=i, records=_records(50, 19, seed=i))
                for i in range(3)]
        out = eng.run(reqs)
        assert len(out) == 3

        snap = obs.snapshot(r)
        assert snap["counters"]['flight.slo_breaches{engine="tree"}'] > 0
        bundles = sorted((tmp_path / "fl").glob("flight-tree-*-slo_breach"))
        assert bundles
        flight = json.loads((bundles[-1] / "flight.json").read_text())
        assert flight["engine"] == "tree" and flight["reason"] == "slo_breach"
        assert flight["waves"] and flight["waves"][-1]["breach"] is True
        assert flight["waves"][-1]["records"] > 0
        trace = json.loads((bundles[-1] / "trace.json").read_text())
        events = trace["traceEvents"]
        assert events and all("ph" in ev for ev in events)
        assert all("ts" in ev for ev in events if ev["ph"] != "M")
        assert any(ev.get("name") == "serve.wave" for ev in events)
        # dump counters name the trigger
        snap = obs.snapshot(r)
        dumps = {k: v for k, v in snap["counters"].items()
                 if k.startswith("flight.dumps")}
        assert any('reason="slo_breach"' in k for k in dumps)
        # the explicit dump path works and bypasses nothing
        manual = eng.dump_flight("debug")
        assert (manual / "flight.json").exists()

    def test_dump_flight_without_recorder_raises(self, tmp_path):
        enc = breadth_first_encode(paper_tree())
        eng = TreeServeEngine(enc, max_batch=64,
                              cache=TuneCache(tmp_path / "c.json"), retune=None)
        with pytest.raises(RuntimeError):
            eng.dump_flight()
