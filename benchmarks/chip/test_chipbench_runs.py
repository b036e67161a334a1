"""CPU tests that drive whole benchmark runs: the refusal without a TPU, and
runs whose timed path is broken underneath, which must read incorrect.

The broken runs skip only the harness's look for a chip: traffic, warm-up,
window, the check against the reference and the result line all run, with
an engine stand-in whose answers carry one planted fault.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import harness, reference, work

ROOT = harness.find_root()
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# every cell's (config, mix)
PAIRS = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]


def _run_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(harness.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_run_refuses_to_start_without_a_tpu(workload, capsys, monkeypatch, tmp_path):
    # main() sets these for its process; monkeypatch puts them back after
    monkeypatch.setenv("REPRO_TUNE_CACHE", "unused")
    monkeypatch.setenv("TPU_LOG_DIR", "unused")
    rc = _run_module().main(["--workload", workload, "--seed", "1", "--seconds", "1",
                             "--out", str(tmp_path)])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err
    assert not any(line.startswith("{") for line in out.out.splitlines())


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", ".jax_cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "seg_cart.stream", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "src/repro" in p.stderr
    assert not p.stdout.strip()


# ---------------------------------------------------------------------------
# runs with a broken timed path
# ---------------------------------------------------------------------------


def _stale(out, records, state, model):
    """A step that hands back its state unchanged: the last answer again."""
    prev = state.get("prev")
    state["prev"] = out
    return prev if prev is not None and prev.shape == out.shape else np.zeros_like(out)


def _half(out, records, state, model):
    """Half of the batch left out: the first half answered twice."""
    h = (records.shape[0] + 1) // 2
    first, _ = reference.classify(model, records[:h])
    return np.concatenate([first, first])[: records.shape[0]]


def _altered(out, records, state, model):
    """One answer altered where it is produced."""
    out = out.copy()
    out[out.shape[0] // 3] = (out[out.shape[0] // 3] + 1) % model.n_classes
    return out


FAULTS = {"stale_state": _stale, "half_batch": _half, "altered_answer": _altered}


class StandIn:
    """The reference in the engine's place, with an optional planted fault."""

    def __init__(self, cell, model, *, cache_path, trace, fault=None):
        self.engine = types.SimpleNamespace()
        self.model, self.fault, self.state = model, fault, {}

    def serve(self, uid, records):
        out, _ = reference.classify(self.model, records)
        return out if self.fault is None else self.fault(out, records, self.state, self.model)

    def counter(self, prefix):
        return 0

    def drain(self):
        pass

    def resolved(self):
        return "stand-in"

    def close(self):
        pass


def _cell(config, traffic):
    """The cell of a configuration and a mix, read from their files."""
    with open(os.path.join(harness.BENCH_DIR, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(harness.BENCH_DIR, "traffic", f"{traffic}.json")) as f:
        mix = json.load(f)
    return harness.Cell(f"{config}.{traffic}", 1, cfg, mix, tuple(BENCH["end_to_end"]), ())


def _small(pair):
    cell = _cell(*pair)
    cfg, mix = copy.deepcopy(cell.config), dict(cell.mix)
    mix["records"] = {"dist": "fixed", "n": 300}
    if mix["loop"] == "open":
        mix["rate_per_s"] = 40.0
    else:
        mix["pool"] = 4
    return harness.Cell(cell.name, cell.chips, cfg, mix, cell.end_to_end, cell.per_layer)


def _run(pair, fault, tmp_path):
    import jax

    def make(cell, model, *, cache_path, trace):
        return StandIn(cell, model, cache_path=cache_path, trace=trace, fault=fault)

    dev = jax.devices()[0]
    return harness.run_cell(
        _small(pair), 2**31 + 99, 0.25, False, t0=0.0,
        device={"platform": dev.platform, "kind": dev.device_kind, "count": 1},
        peaks=work.peaks("TPU v5 lite"), out_dir=str(tmp_path),
        cache_path=str(tmp_path / "tune.json"), compiles=harness.Compiles(),
        make_adapter=make)


@pytest.mark.parametrize("pair", PAIRS)
def test_a_sound_run_reads_correct(pair, tmp_path):
    res = _run(pair, None, tmp_path)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert res["checks"]["mismatched_classes"] == {"value": 0, "limit": 0}
    assert {"setup_s", "records_per_s", "latency_p50_ms", "latency_p95_ms"} <= set(
        res["metrics"])
    json.dumps(res)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("pair", PAIRS)
def test_a_broken_timed_path_reads_incorrect(pair, fault, tmp_path):
    res = _run(pair, FAULTS[fault], tmp_path)
    assert res["correct"] is False
    assert res["checks"]["mismatched_classes"]["value"] > 0


@pytest.mark.parametrize("pair", PAIRS)
def test_the_bf16_control_reads_incorrect(pair):
    """The control in the program's place, through the run's own comparison,
    at a size a test can hold."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_control", os.path.join(harness.BENCH_DIR, "tools", "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    full = _cell(*pair)
    mix = dict(full.mix, records={"dist": "fixed", "n": 8192}, pool=2, rate_per_s=8.0)
    cell = harness.Cell(full.name, full.chips, full.config, mix, full.end_to_end, ())
    row = control.control_checks(cell, 2**31 + 7, 0.25)
    assert row["mismatched_classes"] > harness.CHECK_LIMITS["mismatched_classes"]
