"""Compile-contract audit acceptance gates (ISSUE 8):

(a) an injected f64 upcast in a device search path trips the policy check,
(b) an injected collective in the exact-search program trips the golden
    diff (run against the *committed* ``CONTRACTS.json`` on the real 8-way
    audit mesh, in a subprocess),
plus unit coverage of the diff/policy machinery and a clean-tree subprocess
run proving the committed golden is fresh."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.registry import Entry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=600, env=env)


def _tiny_mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))


TINY = dict(n_series=4096, length=64, w=8, chunk=1024, n_leaves=64,
            k=5, q_batch=4)


# ---------------------------------------------------------------------------
# (a) f64 upcast in a device search path → policy violation
# ---------------------------------------------------------------------------

def test_f64_injection_trips_policy():
    import jax
    import jax.numpy as jnp

    from repro.core import search_device as sd
    from repro.core.distributed import lower_search_sharded

    entry = Entry("search_exact_ed", "test", lower=None)
    mesh = _tiny_mesh()

    clean = contracts.extract_contract(lower_search_sharded(mesh, **TINY))
    assert contracts.policy_violations(entry, clean) == []
    assert "f64" not in clean["dtype_census"]

    orig = sd._exact_knn_sharded

    def upcast(dev, prep, qs, *, k, metric):
        # the classic leak: a wide accumulator that someone "fixes" back
        # down — the f64 ops stay in the compiled program
        return orig(dev, prep,
                    (qs.astype(jnp.float64) * 1.0000001).astype(jnp.float32),
                    k=k, metric=metric)

    with jax.enable_x64(True):
        try:
            sd._exact_knn_sharded = upcast
            bad = contracts.extract_contract(
                lower_search_sharded(mesh, **TINY))
        finally:
            sd._exact_knn_sharded = orig

    assert bad["dtype_census"].get("f64", 0) > 0
    violations = contracts.policy_violations(entry, bad)
    assert violations and "f64" in violations[0]


# ---------------------------------------------------------------------------
# (b) added collective in the exact-search program → golden drift
# ---------------------------------------------------------------------------

INJECT_COLLECTIVE = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis import registry
    from repro.analysis.audit import run_audit
    from repro.core import search_device as sd

    mesh = registry.audit_mesh()
    orig = sd._exact_knn_sharded

    def with_extra_gather(dev, prep, qs, *, k, metric):
        # shard the (replicated) query batch, touch it, gather it back:
        # GSPMD must emit a real all-gather the golden does not declare
        qs = jax.lax.with_sharding_constraint(
            qs, NamedSharding(mesh, P("data", None)))
        qs = qs + 0.0
        qs = jax.lax.with_sharding_constraint(qs, NamedSharding(mesh, P()))
        return orig(dev, prep, qs, k=k, metric=metric)

    sd._exact_knn_sharded = with_extra_gather
    raise SystemExit(run_audit(names=["search_exact_ed"], verbose=False))
"""


def test_collective_injection_trips_golden_diff():
    r = _run_sub(INJECT_COLLECTIVE)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "DRIFT" in r.stderr
    assert "all-gather" in r.stderr      # the injected collective, by name


def test_audit_clean_passes_against_committed_golden():
    r = _run_sub("""
        from repro.analysis.audit import run_audit
        raise SystemExit(run_audit(names=["search_exact_ed",
                                          "build_bottomup"],
                                   verbose=False))
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS" in r.stdout


# ---------------------------------------------------------------------------
# diff / policy machinery (no mesh needed)
# ---------------------------------------------------------------------------

def _contract(**over):
    base = {
        "collectives": {"per_kind": {"all-gather": {"count": 2,
                                                    "bytes": 1024}},
                        "total_bytes": 1024},
        "op_census": {"add": 3, "while": 1},
        "dtype_census": {"f32": 10, "s32": 4},
        "host_calls": {"infeed": 0, "outfeed": 0, "host_callbacks": 0},
        "custom_call_targets": {"TopK": 1},
        "control_flow": {"while": 1, "conditional": 0},
        "donation": {"io_alias_pairs": 0, "alias_bytes": 0},
        "memory": {"argument_bytes": 1000, "output_bytes": 100,
                   "temp_bytes": 500, "alias_bytes": 0, "peak_bytes": 1600},
    }
    base.update(over)
    return base


def test_diff_exact_on_counts():
    g = _contract()
    c = _contract(control_flow={"while": 2, "conditional": 0})
    drift = contracts.diff_contract("p", g, c)
    assert drift == ["p: control_flow.while: 1 -> 2"]


def test_diff_tolerates_small_memory_jitter_only():
    g = _contract()
    c = _contract(memory=dict(_contract()["memory"], temp_bytes=505,
                              peak_bytes=1605))
    assert contracts.diff_contract("p", g, c) == []
    c2 = _contract(memory=dict(_contract()["memory"], temp_bytes=900,
                               peak_bytes=2000))
    drift = contracts.diff_contract("p", g, c2)
    assert any("temp_bytes" in d for d in drift)


def test_diff_catches_new_and_missing_keys():
    g = _contract()
    c = _contract()
    c["collectives"]["per_kind"]["all-reduce"] = {"count": 1, "bytes": 8}
    drift = contracts.diff_contract("p", g, c)
    assert any("all-reduce" in d for d in drift)


def test_policy_flags_host_callbacks_and_collectives():
    e_dev = Entry("p", "test", lower=None)
    bad_cb = _contract(host_calls={"infeed": 0, "outfeed": 0,
                                   "host_callbacks": 2})
    v = contracts.policy_violations(e_dev, bad_cb)
    assert v and "host" in v[0]

    e_local = Entry("q", "test", lower=None, sharded=False)
    v2 = contracts.policy_violations(e_local, _contract())
    assert v2 and "collective" in v2[0]
    assert contracts.policy_violations(e_dev, _contract()) == []


def test_io_alias_pairs_parser():
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (2, {}, must-alias) }, entry_computation_layout={()->f32[]}")
    assert contracts._io_alias_pairs(hlo) == 2
    assert contracts._io_alias_pairs("HloModule m\n") == 0
