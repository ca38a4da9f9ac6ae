"""``correct`` on the CPU at a tiny size: true for the program as it is,
false for the control (the reference in bfloat16 in the program's place)
and for each fault the exact cell can have, planted under the timed path.
The harness's look for a chip is skipped; the rest of a run is driven as
on the chip."""
import time

import numpy as np
import pytest

from bench import harness

SEED = 2**33 + 17


def _run(root, control=False):
    c = harness.load_cell("tiny.exact", root)
    return harness.run_cell(c, SEED, 2.0, False, time.perf_counter(),
                            control=control, chip=False)[0]


def test_sound_run_is_correct(tiny_root):
    r = _run(tiny_root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


def test_control_is_not_correct(tiny_root):
    r = _run(tiny_root, control=True)
    assert not r["correct"], r["checks"]


def _alter_one(ids, d):
    """An answer altered where it is produced: one id of the first query
    swapped for another series."""
    ids = ids.copy()
    ids[0, 0] = (ids[0, 0] + 1) % 8192
    return ids, d


def _drop_half(ids, d):
    """Half of the batch left out: its second half answered with the first
    half's results."""
    ids, d = ids.copy(), d.copy()
    h = len(ids) // 2
    ids[h:2 * h], d[h:2 * h] = ids[:h], d[:h]
    return ids, d


@pytest.mark.parametrize("fault", [_alter_one, _drop_half])
def test_exact_faults_are_caught(tiny_root, monkeypatch, fault):
    from repro.core import search_device as sd
    real = sd.exact_search_device_batch

    def broken(*a, **kw):
        ids, d, vis = real(*a, **kw)
        return (*fault(ids, d), vis)

    monkeypatch.setattr(sd, "exact_search_device_batch", broken)
    r = _run(tiny_root)
    assert not r["correct"], r["checks"]
