"""Shared benchmark utilities: the paper's experimental setup, timed runs.

The paper's workload (§4): UCI Image Segmentation (19 attrs / 7 classes),
classifier with N=31 / 16 leaves / depth 11, dataset of 65 536 records
(256×256 image), 500 timed iterations.  We reproduce it with the synthetic
UCI twin + a CART tree constrained into the same geometry class, falling
back to the deterministic paper-geometry tree when CART lands elsewhere.

Timing conventions mirror the paper:
  * inner time  — the evaluation call only (records already device-resident),
    the analogue of the paper's kernel-only time;
  * outer time  — includes host→device transfer of the record batch and
    device→host transfer of the class assignments (the paper's full-call
    time with cudaMemcpy).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.paper_segmentation import CONFIG as PAPER
from repro.core import (
    CartConfig, breadth_first_encode, eval_serial, paper_tree, train_cart, tree_depth,
)
from repro.data.segmentation import make_segmentation, replicated_dataset


@dataclasses.dataclass
class Workload:
    enc: object          # EncodedTree
    records: np.ndarray  # (65536, 19) float32
    labels: np.ndarray
    depth: int
    d_mu: float


def paper_workload(seed: int = 0, n_records: int | None = None) -> Workload:
    data = make_segmentation(seed)
    root = train_cart(
        data.x_train, data.y_train, PAPER.n_classes,
        CartConfig(max_depth=12, min_samples_split=8, min_gain=4e-3),
    )
    enc = breadth_first_encode(root)
    if not (15 <= enc.n_nodes <= 63):
        enc = breadth_first_encode(paper_tree())
    rec, lab = replicated_dataset(data, n_records or PAPER.dataset_records)
    from repro.core.analysis import mean_traversal_depth, observed_depths

    d_mu = mean_traversal_depth(observed_depths(enc, rec[:2048]))
    return Workload(enc=enc, records=rec, labels=lab, depth=tree_depth(enc), d_mu=d_mu)


@dataclasses.dataclass
class Timing:
    name: str
    mean_us: float
    min_us: float
    max_us: float
    std_us: float
    n: int
    median_us: float = 0.0
    mad_us: float = 0.0  # median absolute deviation — the dispersion the
                         # regression gate trusts (std is outlier-hostage)

    def row(self) -> str:
        return (f"{self.name:32s} {self.mean_us:12.1f} {self.min_us:12.1f} "
                f"{self.max_us:12.1f} {self.std_us:10.2f}")


# Machine-readable perf records: every time_fn call lands here (plus any
# caller-supplied metadata) and run.py drains the buffer into a
# results/BENCH_<name>.json after each bench, so the perf trajectory is
# diffable across PRs instead of living only in stdout tables.
_RECORDS: list[dict] = []


def record_timing(t: Timing, **meta) -> None:
    _RECORDS.append({
        "name": t.name,
        "median_ms": t.median_us / 1e3,
        "mad_ms": t.mad_us / 1e3,
        "mean_ms": t.mean_us / 1e3,
        "min_ms": t.min_us / 1e3,
        "max_ms": t.max_us / 1e3,
        "std_ms": t.std_us / 1e3,
        "iters": t.n,
        "backend": jax.default_backend(),
        **meta,
    })


def drain_records() -> list[dict]:
    out, _RECORDS[:] = list(_RECORDS), []
    return out


def bench_json_path(name: str) -> Path:
    root = Path(os.environ.get("REPRO_BENCH_DIR",
                               Path(__file__).resolve().parent.parent / "results"))
    return root / f"BENCH_{name}.json"


def env_header() -> dict:
    """The environment stamp every committed BENCH_*.json carries.

    A number without its environment is unreproducible: the same bench
    differs by orders of magnitude between a TPU run and interpret-mode
    Pallas on CPU.  This header makes each artifact self-describing —
    rendered by ``results/make_table.py`` above every table.
    """
    import platform

    from repro.kernels.tree_eval import ops as _ops

    dev = jax.devices()[0]
    return {
        "env": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", str(dev)),
            "device_count": jax.device_count(),
            "pallas_interpret": _ops.pallas_interpret(),
            "python": platform.python_version(),
            "platform": platform.platform(),
        }
    }


def history_dir() -> Path:
    """Where the bench trajectory lives (sibling of the BENCH snapshots)."""
    return bench_json_path("_").parent / "history"


def write_bench_json(name: str, entries: list[dict], **header) -> Path:
    path = bench_json_path(name)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "bench": name,
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        **env_header(),
        **header,
        "entries": entries,
    }
    path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    # The snapshot above is overwritten every run; the trajectory only ever
    # appends — results/check_regressions.py gates CI on it.
    from repro.obs.perf import append_history

    append_history(history_dir(), name, payload)
    return path


def time_fn(name: str, fn, *, iters: int = 50, warmup: int = 3, **meta) -> Timing:
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    a = np.asarray(samples)
    med = float(np.median(a))
    t = Timing(name, float(a.mean()), float(a.min()), float(a.max()),
               float(a.std()), iters, med,
               float(np.median(np.abs(a - med))))
    record_timing(t, **meta)
    return t


def header() -> str:
    return (f"{'algorithm':32s} {'mean_us':>12s} {'min_us':>12s} "
            f"{'max_us':>12s} {'std':>10s}")
