"""The four-chip cell's own pieces on the CPU: the collection made shard by
shard, the sharded reference, and a tiny four-device cell run end to end
(in a subprocess with four forced CPU devices, the only place a mesh can
exist under pytest)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import data, data_shards, reference, reference_sharded

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: four shards of whole generator chunks
N, n, S = 4 * data.CHUNK, 16, 4


def _make_shard(seed):
    devices = [jax.devices()[0]] * S
    return lambda s: data_shards.shard(seed, 0, N, n, s, devices)


def test_shards_are_the_collection():
    seed = 2**40 + 9
    whole = np.asarray(data.walks(seed, 0, N, n))
    make = _make_shard(seed)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(make(s)) for s in range(S)]), whole)
    np.testing.assert_array_equal(
        data_shards.host_collection(seed, 0, N, n, [jax.devices()[0]] * S),
        whole)
    with pytest.raises(ValueError):
        data_shards.shard(seed, 0, N + data.CHUNK, n, 0,
                          [jax.devices()[0]] * S)


def test_sharded_reference_is_the_whole_reference():
    seed = 2**35 + 3
    db = data.walks(seed, 0, N, n)
    qs = np.asarray(data.walks(seed, 1, 9, n))
    want_i, want_d = reference.knn(db, qs, 7)
    got_i, got_d = reference_sharded.knn(_make_shard(seed), S, qs, 7)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)
    ids = np.random.default_rng(1).choice(N, (9, 5))
    ids[2, 3:] = -1
    np.testing.assert_array_equal(
        reference_sharded.distances(_make_shard(seed), S, qs, ids),
        reference.distances(db, qs, ids))


CELL = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile, time
sys.path[:0] = ["bench/tests", "src", "."]
import conftest
from bench import check, counts, data, harness

data.CHUNK = 1024                 # four shards of two chunks each
root = conftest.make_root(tempfile.mkdtemp())
b = os.path.join(root, "bench")
with open(os.path.join(b, "configs", "rand256-x4.json")) as fh:
    cfg = json.load(fh)
cfg["name"] = "tiny-x4"
cfg["collection"].update(n_series=conftest.TINY["n_series"],
                         length=conftest.TINY["length"])
cfg["params"].update(th=conftest.TINY["th"])
with open(os.path.join(b, "configs", "tiny-x4.json"), "w") as fh:
    json.dump(cfg, fh)
with open(os.path.join(b, "traffic", "exact-b64-mesh.json")) as fh:
    tr = json.load(fh)
tr.update(batch=8, pool=64, sample=32)
with open(os.path.join(b, "traffic", "tiny-exact-mesh.json"), "w") as fh:
    json.dump(tr, fh)
spec_path = os.path.join(root, "BENCHMARK.json")
with open(spec_path) as fh:
    spec = json.load(fh)
spec["workloads"].append({"name": "tiny-x4.exact", "config": "tiny-x4",
                          "traffic": "tiny-exact-mesh", "chips": 4,
                          "why": "CPU test"})
for m in spec["end_to_end"] + spec["per_layer"]:
    if "rand256-x4.exact-b64" in m.get("workloads", []):
        m["workloads"].append("tiny-x4.exact")
with open(spec_path, "w") as fh:
    json.dump(spec, fh)

cell = harness.load_cell("tiny-x4.exact", root)
seed = 2**33 + 29
sound, out = harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                              chip=False)
control, _ = check.verdict(out.check(True), cell.traffic["limits"])
counts.peaks = lambda kind, path=None: {"hbm_bytes_per_s": 1e9,
                                        "bf16_flops_per_s": 1e12}
traced, _ = harness.run_cell(cell, seed + 1, 1.0, True, time.perf_counter(),
                             chip=False)
print(json.dumps({"sound": sound, "control_correct": control,
                  "traced": traced,
                  "per_layer": [m["name"] for m in cell.per_layer]}))
"""


@pytest.fixture(scope="module")
def tiny_x4():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "-c", CELL], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_tiny_four_device_cell_is_correct(tiny_x4):
    r = tiny_x4["sound"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4 and r["attempted"] > 0
    assert set(r["metrics"]) == {"exact_qps", "setup_s"}


def test_tiny_four_device_control_is_not_correct(tiny_x4):
    assert tiny_x4["control_correct"] is False


def test_tiny_four_device_cell_reads_every_per_layer_metric(tiny_x4):
    got = tiny_x4["traced"]["metrics"]
    assert tiny_x4["traced"]["correct"]
    assert set(got) == set(tiny_x4["per_layer"])
    assert "exact_scan_roofline" not in got
    assert 0 < got["exact_shard_balance"]["value"] <= 100
    assert got["exact_mesh_scan_roofline"]["value"] > 0
