"""``exact_fold_share``: the merged spans at which no query held two
candidates in one 128-column lane, over the spans merged, on a hand-made
run and on a whole traced run of the tiny cell on the CPU; a program that
does not count lane collisions, or has no ``repro.obs``, reads nothing and
raises nothing."""
import time
import types

import pytest

from bench import harness

MS = 1_000_000


def _run():
    """Set-up from 1 s to 10 s, the window from 10 s to 11 s."""
    return {"ctx": types.SimpleNamespace(t_start=1.0, setup_s=9.0,
                                         window_s=1.0),
            "counters": {}, "trace": {}, "metrics": {}, "device": {}}


def _fake(monkeypatch, collided):
    """Three exact calls, one in set-up and two in the window; with
    ``collided`` each also counts the merged spans whose lanes collided."""
    from repro import obs
    S, C = obs.Span, obs.Count
    call = {"Q": 4, "k": 5, "chunk": 100}
    spans = [S("dumpy.exact.call", 8, 0, 9_600 * MS, 9_900 * MS, dict(call)),
             S("dumpy.exact.call", 10, 0, 10_000 * MS, 10_305 * MS,
               dict(call)),
             S("dumpy.exact.call", 14, 0, 10_400 * MS, 10_710 * MS,
               dict(call))]
    counts = [C("exact.spans_merged", 50, 9_800 * MS, 8),
              C("exact.spans_merged", 4, 10_290 * MS, 10),
              C("exact.spans_merged", 16, 10_690 * MS, 14)]
    if collided:
        counts += [C("exact.spans_collided", 50, 9_800 * MS, 8),
                   C("exact.spans_collided", 1, 10_290 * MS, 10),
                   C("exact.spans_collided", 4, 10_690 * MS, 14)]
    monkeypatch.setattr(obs, "spans", lambda: list(spans))
    monkeypatch.setattr(obs, "counters", lambda: list(counts))


def _read(run):
    return harness.reader("exact_fold_share").read(run)


def test_share_over_the_window_calls(monkeypatch):
    _fake(monkeypatch, collided=True)
    # (1 + 4) collided of (4 + 16) merged; the set-up call is not read
    assert _read(_run()) == pytest.approx(100 * (1 - 5 / 20))


def test_a_program_without_the_counter_reads_nothing(monkeypatch):
    _fake(monkeypatch, collided=False)
    assert _read(_run()) is None


def test_without_the_program_spans(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(__import__("sys").modules, "repro.obs", None)
    assert _read(_run()) is None


def test_traced_tiny_run_reads_the_share(tiny_root, monkeypatch):
    from bench import counts
    monkeypatch.setattr(counts, "peaks", lambda kind, path=None: {
        "hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12})
    cell = harness.load_cell("tiny.exact", tiny_root)
    assert "exact_fold_share" in {m["name"] for m in cell.per_layer}
    r, _ = harness.run_cell(cell, 2**31 + 13, 1.0, True, time.perf_counter(),
                            chip=False)
    assert r["correct"], r["checks"]
    share = r["metrics"]["exact_fold_share"]["value"]
    assert 0 <= share < 100
