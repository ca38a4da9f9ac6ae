"""Shared fixtures of the benchmark's own tests (run with
``python -m pytest bench/tests`` from the checkout root, on the CPU)."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

#: a collection small enough for the CPU, with leaves small enough that
#: extended search walks a real tree
TINY = {"n_series": 8192, "length": 64, "th": 256}


def tiny_config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "rand256.json")) as fh:
        cfg = json.load(fh)
    cfg["name"] = name
    cfg["collection"].update(n_series=TINY["n_series"], length=TINY["length"])
    cfg["params"].update(th=TINY["th"])
    return cfg


def make_root(dst: str) -> str:
    """A copy of the benchmark (``BENCHMARK.json`` and ``bench/``) whose
    cell ``tiny.exact`` runs on the CPU in seconds: a tiny configuration
    and small batches. Only new files are added; none is edited."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = os.path.join(dst, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as fh:
        json.dump(tiny_config("tiny"), fh)
    with open(os.path.join(ROOT, "bench", "traffic", "exact-b64.json")) as fh:
        ex = json.load(fh)
    ex.update(batch=8, pool=64, sample=32)
    with open(os.path.join(b, "traffic", "tiny-exact.json"), "w") as fh:
        json.dump(ex, fh)
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["workloads"].append({"name": "tiny.exact", "config": "tiny",
                              "traffic": "tiny-exact", "chips": 1,
                              "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rand256.exact-b64" in m.get("workloads", []):
            m["workloads"].append("tiny.exact")
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench_root")))
