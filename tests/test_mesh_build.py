"""The device build on a mesh: each shard's rows laid out on the device
that owns it, at the cuts ``DeviceIndex`` uses, and the sharded exact
search over them.  Runs in one subprocess on four forced CPU devices (the
only place a multi-device mesh can exist under pytest); each test reads
one finding of it."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import json
import numpy as np
import jax
from repro import obs
from repro.core.build import DumpyParams
from repro.core.index import DumpyIndex
from repro.core.split import SplitParams
from repro.core.search import exact_search
from repro.core import search_device as sd
from repro.data.series import random_walks
from repro.distributed.sharding import make_mesh

assert len(jax.devices()) == 4
N, n = 1 << 14, 64
db = random_walks(N, n, seed=5)
params = DumpyParams(split=SplitParams(th=256))
mesh = make_mesh((4,), ("data",))
one = DumpyIndex.build(db, params, backend="device")
four = DumpyIndex.build(db, params, backend="device", mesh=mesh)
whole = [a.shape for a in jax.live_arrays() if a.shape == (N, n)]
out = {"layout": {
    "order": bool(np.array_equal(one.flat.order, four.flat.order)),
    "offsets": bool(np.array_equal(one.flat.leaf_offsets,
                                   four.flat.leaf_offsets)),
    "no_device_holds_the_collection": len(whole) == 1}}   # ``one``'s rows

ref = one.device_index(n_shards=4)                  # one device, 4 shards
qs = random_walks(6, n, seed=11).astype(np.float32)
m = obs.mark()
ids4, d4, vis4 = sd.exact_search_device_batch(four, qs, 10, mesh=mesh)
got = {c.name: c.n for c in obs.counters() if c.span > m}
dev = four._device_cache[(2048, 4, mesh)][0]
parts = four._db_shards[1]
Tp = dev.shard_rows
out["layout"]["row_bounds"] = dev.row_bounds == ref.row_bounds
out["layout"]["leaf_bounds"] = dev.leaf_bounds == ref.leaf_bounds
out["layout"]["slab"] = bool(np.array_equal(np.asarray(dev.db),
                                            np.asarray(ref.db)))
out["shards"] = []
for sh in dev.db.addressable_shards:
    s = sh.index[0].start or 0
    out["shards"].append({
        "shape": list(sh.data.shape) == [1, Tp, n],
        "rows": bool(np.array_equal(np.asarray(sh.data),
                                    np.asarray(ref.db)[s:s + 1])),
        "no_copy": sh.data.unsafe_buffer_pointer()
        == parts[sh.device].unsafe_buffer_pointer()})

ids1, d1, _ = sd.exact_search_device_batch(one, qs, 10)       # one shard
host = [exact_search(one, q, 10)[:2] for q in qs]
out["search"] = {
    "one_shard": bool((ids1 == ids4).all() and (d1 == d4).all()),
    "host": all(np.array_equal(ids4[i], np.asarray(h_i))
                and np.array_equal(d4[i], np.asarray(h_d, np.float32))
                for i, (h_i, h_d) in enumerate(host))}

# a collection below ``th``: one leaf, so three of the four shards are empty
small = DumpyIndex.build(db[:200], params, backend="device", mesh=mesh)
ids_s, d_s, _ = sd.exact_search_device_batch(small, qs, 10, mesh=mesh)
out["search"]["one_leaf"] = all(
    np.array_equal(ids_s[i], np.asarray(exact_search(small, q, 10)[0]))
    for i, q in enumerate(qs))

m = obs.mark()
sd.exact_search_device_batch(one, qs, 10, dev=ref)     # the same 4 shards
on_one = {c.name: c.n for c in obs.counters() if c.span > m}
out["walk"] = {"walked": got["exact.spans_walked"],
               "most": got[sd.SPANS_WALKED_MAX],
               "most_on_one_device": on_one[sd.SPANS_WALKED_MAX],
               "shards": 4}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_build_layout_is_the_one_device_build(found):
    assert found["layout"] == {k: True for k in found["layout"]}


def test_each_device_holds_only_its_shard(found):
    assert len(found["shards"]) == 4
    for s in found["shards"]:
        assert s == {"shape": True, "rows": True, "no_copy": True}


def test_mesh_search_is_bitwise_one_shard_and_host(found):
    assert found["search"] == {"one_shard": True, "host": True,
                               "one_leaf": True}


def test_spans_walked_max_bounds_the_shards(found):
    w = found["walk"]
    # the most one shard walked: at least the mean walk, at most the sum,
    # and the same walk as the same four shards on one device
    assert w["walked"] / w["shards"] <= w["most"] <= w["walked"]
    assert w["most"] == w["most_on_one_device"]
