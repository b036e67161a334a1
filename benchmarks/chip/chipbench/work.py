"""The work a wave needs, whatever kernel does it, and the least time for it.

Counted per the algorithm, not per implementation:

* every record is read once, at the configuration's attribute count, in
  float32;
* every tree's nodes are read once per wave: attribute index, threshold,
  child index and class, 4 bytes each, over the tree's own node count;
* one comparison per internal node on each record's path in the reference
  descent, plus one vote per tree and record where there is more than one
  tree;
* one int32 class is written per record.

Padding, layout, the variant that runs and the speculative algorithm's
work on nodes off the path are not counted.  The least time is the larger
of the operations over the chip's highest published operation rate and
the bytes over its published memory bandwidth: no published peak is the
rate of a comparison on the vector unit, so the highest one keeps the
bound a true lower bound.
"""

from __future__ import annotations

import dataclasses
import json
import os

NODE_BYTES = 16          # attr, threshold, child, class: 4 bytes each
RECORD_VALUE_BYTES = 4   # float32
CLASS_BYTES = 4          # int32

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the table of published peaks."""


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    op_per_s: float          # the highest published operation rate
    hbm_byte_per_s: float
    hbm_bytes: float
    source: str


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Peaks:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(path) as f:
        table = json.load(f)
    row = table["devices"].get(device_kind)
    if row is None:
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r} "
                            f"in {os.path.basename(path)}")
    return Peaks(device_kind, max(row["bf16_flop_per_s"], row["int8_op_per_s"]),
                 row["hbm_byte_per_s"], row["hbm_bytes"], table["source"])


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def least_s(self, p: Peaks) -> float:
        return max(self.ops / p.op_per_s, self.bytes / p.hbm_byte_per_s)

    def bound(self, p: Peaks) -> str:
        return "ops" if self.ops / p.op_per_s > self.bytes / p.hbm_byte_per_s else "bytes"


def wave_work(records: int, comparisons: float, *, n_attrs: int,
              tree_nodes: tuple[int, ...]) -> Work:
    """The work of one wave of ``records`` whose reference paths hold
    ``comparisons`` internal nodes in all."""
    n_trees = len(tree_nodes)
    votes = records * n_trees if n_trees > 1 else 0
    byts = (records * n_attrs * RECORD_VALUE_BYTES
            + sum(tree_nodes) * NODE_BYTES
            + records * CLASS_BYTES)
    return Work(float(comparisons + votes), float(byts))
