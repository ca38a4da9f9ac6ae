"""Stand up a configuration's deployment: the collection, the index, its
device state. Shared by every driver; timed into the run's set-up."""
from __future__ import annotations

import numpy as np

from bench import data

#: seed streams (bench.data.key): the collection, then the query pool
COLLECTION, QUERIES = 0, 1


def params(cfg: dict):
    from repro.core.build import DumpyParams
    from repro.core.sax import SaxParams
    from repro.core.split import SplitParams
    p = cfg["params"]
    return DumpyParams(sax=SaxParams(w=p["w"], b=p["b"]),
                       split=SplitParams(th=p["th"], alpha=p["alpha"]),
                       fuzzy_f=p["fuzzy_f"], max_replica=p["max_replica"])


def collection_device(cfg: dict, seed: int):
    c = cfg["collection"]
    return data.walks(seed, COLLECTION, c["n_series"], c["length"])


def queries(cfg: dict, seed: int, count: int) -> np.ndarray:
    return np.asarray(data.walks(seed, QUERIES, count,
                                 cfg["collection"]["length"]))


def build(ctx):
    """``(index, dev)``: the collection made on the device and copied to the
    host once, the index built by the device backend, its ``DeviceIndex``
    resident. Times ``data_s`` and ``build_s``."""
    import jax
    from repro.core.index import DumpyIndex
    with ctx.timed("data_s"):
        db = np.asarray(collection_device(ctx.config, ctx.seed))
    with ctx.timed("build_s"):
        index = DumpyIndex.build(db, params(ctx.config), backend="device")
        dev = index.device_index()
        jax.block_until_ready(dev)
    return index, dev
