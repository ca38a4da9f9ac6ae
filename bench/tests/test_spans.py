"""The readers of the program's spans and counters, on a hand-made run and
on a whole traced run of the tiny cell on the CPU; without ``repro.obs``
they read nothing and raise nothing."""
import time
import types

import pytest

from bench import harness

NEW = ("exact_prep_ms", "exact_finalize_ms", "exact_scan_useful",
       "window_compiles", "setup_compile_s", "build_encode_s",
       "build_split_s", "build_layout_s", "build_upload_s")
MS = 1_000_000


def _run(t_start=1.0, setup_s=9.0, window_s=1.0):
    return {"ctx": types.SimpleNamespace(t_start=t_start, setup_s=setup_s,
                                         window_s=window_s),
            "counters": {}, "trace": {}, "metrics": {}, "device": {}}


def _records(obs):
    """Set-up from 1 s to 10 s, the window from 10 s to 11 s, ns."""
    S, C = obs.Span, obs.Count
    s = 1_000 * MS
    call = {"Q": 4, "k": 5, "chunk": 100}
    spans = [
        S("dumpy.compile", 1, 0, 2 * s, 2 * s + 300 * MS, {"fun": "a"}),
        S("dumpy.build.encode", 3, 2, 3 * s, 4 * s, None),
        S("dumpy.build.split", 4, 2, 4 * s, 6 * s, None),
        S("dumpy.compile", 5, 6, 6 * s, 6 * s + 200 * MS, {"fun": "b"}),
        S("dumpy.build.layout", 6, 2, 6 * s, 7 * s, None),
        S("dumpy.build", 2, 0, 3 * s, 7 * s + 10 * MS, None),
        S("dumpy.device_index", 7, 0, 8 * s, 9 * s + 500 * MS, None),
        # a warm-up call in set-up: not a window call
        S("dumpy.exact.prep", 9, 8, 9_600 * MS, 9_700 * MS, None),
        S("dumpy.exact.call", 8, 0, 9_600 * MS, 9_900 * MS, dict(call)),
        # two calls in the window; the first compiles inside its prep
        S("dumpy.compile", 12, 11, 10_002 * MS, 10_004 * MS, {"fun": "c"}),
        S("dumpy.exact.prep", 11, 10, 10_000 * MS, 10_010 * MS, None),
        S("dumpy.exact.finalize", 13, 10, 10_300 * MS, 10_303 * MS, None),
        S("dumpy.exact.call", 10, 0, 10_000 * MS, 10_305 * MS, dict(call)),
        S("dumpy.exact.prep", 15, 14, 10_400 * MS, 10_404 * MS, None),
        S("dumpy.exact.finalize", 16, 14, 10_700 * MS, 10_705 * MS, None),
        S("dumpy.exact.call", 14, 0, 10_400 * MS, 10_710 * MS, dict(call)),
        # after the window (the check): not read
        S("dumpy.compile", 17, 0, 12 * s, 12 * s + MS, {"fun": "d"}),
    ]
    counts = [C("exact.spans_walked", 50, 9_800 * MS, 8),
              C("exact.spans_walked", 10, 10_290 * MS, 10),
              C("exact.rows_live", 900, 10_290 * MS, 10),
              C("exact.pairs_needed", 2_000, 10_290 * MS, 10),
              C("exact.spans_walked", 20, 10_690 * MS, 14),
              C("exact.pairs_needed", 1_000, 10_690 * MS, 14)]
    return spans, counts


@pytest.fixture
def fake_obs(monkeypatch):
    from repro import obs
    spans, counts = _records(obs)
    monkeypatch.setattr(obs, "spans", lambda: list(spans))
    monkeypatch.setattr(obs, "counters", lambda: list(counts))
    return obs


def _read(name, run):
    return harness.reader(name).read(run)


def test_readers_on_a_hand_made_run(fake_obs):
    run = _run()
    # prep self time: 10 ms less the 2 ms compile, and 4 ms; two calls
    assert _read("exact_prep_ms", run) == pytest.approx((8 + 4) / 2)
    assert _read("exact_finalize_ms", run) == pytest.approx((3 + 5) / 2)
    # (2,000 + 1,000) needed of (10 + 20) spans × 100 rows × 4 queries
    assert _read("exact_scan_useful", run) == pytest.approx(
        100 * 3_000 / (30 * 100 * 4))
    assert _read("window_compiles", run) == 1
    assert _read("setup_compile_s", run) == pytest.approx(0.5)
    assert _read("build_encode_s", run) == pytest.approx(1.0)
    assert _read("build_split_s", run) == pytest.approx(2.0)
    assert _read("build_layout_s", run) == pytest.approx(1.0)
    assert _read("build_upload_s", run) == pytest.approx(1.5)


def test_readers_clip_to_the_window(fake_obs):
    # a window that holds no call: the per-call metrics read nothing
    run = _run(setup_s=9.2, window_s=0.3)
    assert _read("exact_prep_ms", run) is None
    assert _read("exact_finalize_ms", run) is None
    assert _read("exact_scan_useful", run) is None
    assert _read("window_compiles", run) == 0
    # set-up ends at 10.2 s: the call's in-prep compile now counts there
    assert _read("setup_compile_s", run) == pytest.approx(0.502)


def test_readers_without_the_program_spans(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(__import__("sys").modules, "repro.obs", None)
    run = _run()
    for name in NEW:
        assert _read(name, run) is None, name


def test_traced_tiny_run_reads_every_new_metric(tiny_root, monkeypatch):
    from bench import counts
    monkeypatch.setattr(counts, "peaks", lambda kind, path=None: {
        "hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12})
    cell = harness.load_cell("tiny.exact", tiny_root)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    r, _ = harness.run_cell(cell, 2**31 + 7, 1.0, True, time.perf_counter(),
                            chip=False)
    assert r["correct"], r["checks"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    assert got["window_compiles"] == 0
    assert 0 < got["exact_scan_useful"] <= 100
    assert got["exact_prep_ms"] > 0 and got["exact_finalize_ms"] > 0
    stages = sum(got[f"build_{s}_s"] for s in ("encode", "split", "layout",
                                                "upload"))
    assert stages <= got["build_s"] * 1.0001
