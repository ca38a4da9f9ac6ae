"""The harness finds cells, configurations, traffic mixes and metrics by
name; ``BENCHMARK.json`` keeps to its contract; no chip means no result."""
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _digest(root):
    out = {}
    for p in sorted(glob.glob(os.path.join(root, "bench", "**", "*"),
                              recursive=True)):
        if os.path.isfile(p) and "__pycache__" not in p:
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tiny_root, tmp_path, monkeypatch):
    root = str(tmp_path / "copy")
    shutil.copytree(tiny_root, root)
    before = _digest(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny.json")) as fh:
        cfg = json.load(fh)
    cfg.update(name="tiny-long")
    cfg["collection"]["length"] = 128
    with open(os.path.join(b, "configs", "tiny-long.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(b, "traffic", "tiny-exact.json")) as fh:
        tr = json.load(fh)
    tr.update(batch=4, pool=32)
    with open(os.path.join(b, "traffic", "exact-b4.json"), "w") as fh:
        json.dump(tr, fh)
    with open(os.path.join(b, "metrics", "exact_calls.py"), "w") as fh:
        fh.write('def read(run):\n'
                 '    return run["counters"].get("exact_calls")\n')
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    spec["workloads"].append({"name": "tiny-long.exact-b4",
                              "config": "tiny-long", "traffic": "exact-b4",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.exact" in m.get("workloads", []):
            m["workloads"].append("tiny-long.exact-b4")
    spec["per_layer"].append({"name": "exact_calls", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "search programs", "moves": "exact_qps",
                              "workloads": ["tiny-long.exact-b4"]})
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before

    cell = harness.load_cell("tiny-long.exact-b4", root)
    assert cell.config["collection"]["length"] == 128
    assert cell.traffic["batch"] == 4
    assert "exact_calls" in [m["name"] for m in cell.per_layer]
    from bench import counts
    monkeypatch.setattr(counts, "peaks", lambda kind, path=None: {
        "hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12})
    r, _ = harness.run_cell(cell, 99, 1.0, True, time.perf_counter(),
                            chip=False)
    assert r["correct"], r["checks"]
    assert r["metrics"]["exact_calls"]["value"] >= 1
    assert r["metrics"]["exact_calls"]["unit"] == "calls"


def _cmd(cwd, env=None):
    spec = _spec()
    return subprocess.run(
        spec["command"] + ["--workload", spec["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout and "metrics" not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    spec = _spec()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(str(tmp_path), env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_json_keeps_its_contract():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = 24
    assert (2 + 14 * cells) * (spec["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
    names = [c["name"] for c in spec["configs"]]
    cells = {w["name"]: w for w in spec["workloads"]}
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len(set(names)) == len(names)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as fh:
            body = json.load(fh)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert body["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in cells.values())
    pairs = set()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= set(cells)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert callable(harness.reader(m["name"]).read)
    for name in cells:
        cell = harness.load_cell(name)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        assert all(m["moves"] in got for m in cell.per_layer)
        assert "limits" in cell.traffic
    assert len(json.dumps(spec)) <= 64 * 1024


def test_run_module_imports_nothing_heavy():
    # the entry point parses and checks the checkout before touching JAX
    p = subprocess.run([sys.executable, "-c",
                        "import sys, bench.run; "
                        "print('jax' in sys.modules)"],
                       cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert p.stdout.strip() == "False"
