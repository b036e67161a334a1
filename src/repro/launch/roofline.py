"""Roofline-term derivation from a compiled dry-run cell.

Device peaks live in one table, :data:`DEVICE_PEAKS`, keyed by JAX's
``device_kind``.  The dry-run cells target TPU v5e (per chip: 197 TFLOP/s
bf16, 819 GB/s HBM, ~50 GB/s/link ICI).  Terms in seconds:

    compute    = HLO_FLOPs / (chips × 197e12)
    memory     = HLO_bytes / (chips × 819e9)
    collective = collective_bytes_per_chip / 50e9

``cost_analysis`` reports whole-program FLOPs/bytes (already per-partition in
SPMD mode — verified against per-chip expectations in tests); collective
bytes come from parsing the compiled HLO (utils/hlo.py) and are per-chip wire
bytes.  MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) measures how much
of the compiled compute is "useful" (remat/dispatch overhead shows up as a
ratio < 1).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""

    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float       # capacity
    ici_bw: float          # bytes/s per link, effective


# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  ICI is the
# effective per-link figure the dry-run collective term has always used.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9),
}


def device_peaks(device_kind: str | None = None) -> DevicePeaks | None:
    """Peaks for ``device_kind`` (default: the first JAX device's), or None
    for a device the table does not hold — no other device's numbers stand
    in for it."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


# The dry-run cells' target chip (launch/dryrun.py compiles for v5e).
_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bw
ICI_BW = _V5E.ici_bw


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    step: str
    mesh: str
    chips: int
    hlo_flops: float           # per chip
    hlo_bytes: float           # per chip
    coll_bytes: float          # per chip
    coll_summary: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float         # whole-step useful FLOPs (6ND)
    peak_memory_bytes: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step bound spent on useful model FLOPs: the score
        axis — (model_flops/chips/peak) / max(compute, memory, collective)."""
        ideal = self.model_flops / self.chips / PEAK_FLOPS
        return ideal / self.bound_s if self.bound_s else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "step": self.step,
            "mesh": self.mesh, "chips": self.chips,
            "hlo_flops_per_chip": self.hlo_flops,
            "hlo_bytes_per_chip": self.hlo_bytes,
            "coll_bytes_per_chip": self.coll_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "coll_summary": self.coll_summary,
            "peak_memory_bytes": self.peak_memory_bytes,
        }


def model_flops_for(cfg, shape) -> float:
    """6·N·D useful-FLOPs estimate for the step."""
    n = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens      # forward only
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def terms_from_compiled(
    *, arch: str, shape, step: str, mesh_name: str, chips: int,
    cost: dict, coll_stats, cfg, memory_stats: Optional[dict] = None,
) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    coll = float(getattr(coll_stats, "coll_bytes", 0.0) or getattr(coll_stats, "total_bytes", 0.0))
    return RooflineTerms(
        arch=arch,
        shape=shape.name,
        step=step,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=bytes_,
        coll_bytes=coll,
        coll_summary=(coll_stats.coll_summary() if hasattr(coll_stats, "coll_summary")
                      else coll_stats.summary()),
        compute_s=flops / PEAK_FLOPS,
        memory_s=bytes_ / HBM_BW,
        collective_s=coll / ICI_BW,
        model_flops=model_flops_for(cfg, shape),
        peak_memory_bytes=float((memory_stats or {}).get("temp_size_in_bytes", 0.0)),
    )


def format_table(rows: list[dict]) -> str:
    cols = ["arch", "shape", "step", "mesh", "compute_s", "memory_s",
            "collective_s", "dominant", "useful_ratio", "roofline_fraction"]
    hdr = " | ".join(f"{c:>18s}" for c in cols)
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        vals = []
        for c in cols:
            v = r[c]
            vals.append(f"{v:>18.3e}" if isinstance(v, float) else f"{str(v):>18s}")
        lines.append(" | ".join(vals))
    return "\n".join(lines)


def save_rows(path: str, rows: list[dict]) -> None:
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
