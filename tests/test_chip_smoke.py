"""chip_smoke.py's phases at a tiny size on the CPU (Pallas interpreted).

The script itself refuses to run without a TPU; these tests keep its phase
functions — the served paths and their exactness checks — working in the
tier-1 suite.  ``engines=("pallas",)`` routes every phase through the
Pallas kernels, which the CPU backend interprets.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = dict(engines=("pallas",), compiled_kernels=False)


def test_tree_phase_tiny(smoke):
    out = smoke.tree_phase(n_records=512, n_requests=2, **TINY)
    assert out["candidate"].startswith("pallas_")
    assert out["tune_failures"] == 0 and out["profiler_errors"] == 0


def test_forest_phase_tiny(smoke):
    out = smoke.forest_phase(n_records=256, n_waves=2, n_trees=4, max_depth=3, **TINY)
    assert out["candidates"] and out["profiler_errors"] == 0


def test_anytime_phase_tiny(smoke):
    out = smoke.anytime_phase(n_records=256, n_trees=5, max_depth=3, **TINY)
    assert out["candidate"].startswith("cascade/pallas")


def test_main_refuses_the_cpu():
    """No TPU: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
