"""Work counts against rows counted by hand on a tiny index, and the peak
table."""
import numpy as np
import pytest

from bench import counts


@pytest.fixture(scope="module")
def tiny_index():
    from repro.core.build import DumpyParams
    from repro.core.index import DumpyIndex
    from repro.core.sax import SaxParams
    from repro.core.split import SplitParams
    from repro.data.series import random_walks
    db = random_walks(4096, 64, seed=7)
    p = DumpyParams(sax=SaxParams(w=16, b=8), split=SplitParams(th=128))
    idx = DumpyIndex.build(db, p, backend="device")
    return idx, idx.device_index(chunk=256)


def test_exact_work_matches_hand_count(tiny_index):
    from repro.core.search_device import exact_search_device_batch
    from repro.data.series import query_workload
    idx, dev = tiny_index
    qs = query_workload(8, 64, seed=9)
    _, _, vis = exact_search_device_batch(idx, qs, 5, chunk=256, dev=dev)
    rows = np.asarray(dev.win_size).reshape(-1)
    nbytes, flops = counts.exact_work([vis], rows, 64)
    srt = sorted(rows.tolist())
    assert nbytes == sum(srt[:int(max(vis))]) * 64 * 4
    assert flops == sum(sum(srt[:int(v)]) for v in vis) * 2 * 64
    assert 0 < nbytes <= len(rows) * 256 * 64 * 4


def test_roofline_share_and_peaks():
    peak = counts.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    # bytes-bound: 819 MB in 2 ms is 50% of 1 ms
    assert counts.roofline_pct(819e6, 1.0, 2e-3, peak) == pytest.approx(50.0)
    assert counts.roofline_pct(0.0, 0.0, 1.0, peak) is None
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")
