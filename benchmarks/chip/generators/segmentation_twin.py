"""Records of the paper's workload (§4.1): the segmentation table, tiled.

The table is the synthetic twin of UCI Image Segmentation (19 attributes,
7 classes, 2,310 + 2,099 records): per class, five correlated groups of
attributes, each a Gaussian around a class mean.  A request is the paper's
timing workload: the combined table randomly permuted again and again and
tiled out to the request's size (a 256 x 256 image is 65,536 records).
"""

import numpy as np

GROUPS = ((0, 4), (4, 8), (8, 12), (12, 16), (16, 19))


def table(seed: int, n_attrs: int, n_classes: int, n_rows: int) -> np.ndarray:
    """The combined train + test table, shuffled; float32 (n_rows, n_attrs)."""
    gen = np.random.default_rng(seed)
    per = np.full((n_classes,), n_rows // n_classes)
    per[: n_rows % n_classes] += 1
    xs = []
    for c in range(n_classes):
        x = np.zeros((per[c], n_attrs))
        for lo, hi in GROUPS:
            mean = gen.normal(0, 2.0, size=(hi - lo,))
            base = gen.normal(size=(per[c], 1))
            x[:, lo:hi] = mean + base + 0.6 * gen.normal(size=(per[c], hi - lo))
        xs.append(x)
    x = np.concatenate(xs).astype(np.float32)
    return x[gen.permutation(n_rows)]


class Source:
    def __init__(self, config: dict):
        spec = config["records"]
        self.table = table(int(spec["table_seed"]), int(config["n_attrs"]),
                           int(config["n_classes"]), int(spec["table_rows"]))

    def draw(self, gen: np.random.Generator, n: int) -> np.ndarray:
        rows = self.table.shape[0]
        reps = -(-n // rows)
        idx = np.concatenate([gen.permutation(rows) for _ in range(reps)])[:n]
        return self.table[idx]
