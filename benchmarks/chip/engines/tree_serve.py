"""The paper's tree as a service: ``repro.serve.TreeServeEngine``."""

import numpy as np


def make(model, options: dict, *, cache_path: str, registry, tracer):
    from repro.core import EncodedTree
    from repro.serve import TreeServeEngine
    from repro.tune import TuneCache

    if model.n_trees != 1:
        raise ValueError("tree_serve serves one tree")
    n = model.n_nodes[0]
    enc = EncodedTree(model.attr[0, :n], model.threshold[0, :n], model.child[0, :n],
                      model.cls[0, :n])
    return TreeServeEngine(enc, cache=TuneCache(cache_path), registry=registry,
                           tracer=tracer, **options)


def serve(engine, uid: int, records: np.ndarray) -> np.ndarray:
    """One request through the engine; its classes on the host."""
    from repro.serve import TreeRequest

    (req,) = engine.run([TreeRequest(uid=uid, records=records)])
    return np.asarray(req.out)
