"""Pallas kernel microbenchmarks (interpret mode on CPU — correctness-path
timing; the derived column carries the analytic TPU-v5e roofline estimate
for the same shapes)."""
from __future__ import annotations

import json
import os

import numpy as np
import jax.numpy as jnp

from repro.kernels import ops
from . import common

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "bench", "peaks.json")


def v5e_peaks() -> tuple[float, float]:
    """(HBM bytes/s, bf16 FLOP/s) of one v5e, from the on-chip benchmark's
    table of published peaks."""
    with open(PEAKS) as fh:
        v5e = json.load(fh)["devices"]["TPU v5 lite"]
    return v5e["hbm_bytes_per_s"], v5e["bf16_flops_per_s"]


def run() -> list[tuple[str, float, str]]:
    hbm_bw, peak_flops = v5e_peaks()
    rng = np.random.default_rng(0)
    rows = []

    x = jnp.asarray(rng.standard_normal((4096, 256)), jnp.float32)
    (paa, sax), dt = common.timed(
        lambda: tuple(map(lambda a: a.block_until_ready(),
                          ops.sax_encode(x, 16, 8))), repeat=3)
    bytes_moved = x.size * 4 + paa.size * 4 + sax.size * 4
    est = bytes_moved / hbm_bw * 1e6
    rows.append(("kernel/sax_encode/4096x256", dt * 1e6,
                 f"v5e_est_us={est:.2f};mem_bound=True"))

    q = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    xs = jnp.asarray(rng.standard_normal((4096, 256)), jnp.float32)
    d, dt = common.timed(lambda: ops.pairwise_l2(q, xs).block_until_ready(),
                         repeat=3)
    flops = 2 * 64 * 4096 * 256
    est = max(flops / peak_flops, (q.size + xs.size + d.size) * 4 / hbm_bw) * 1e6
    rows.append(("kernel/pairwise_l2/64x4096x256", dt * 1e6,
                 f"v5e_est_us={est:.2f}"))

    lo = jnp.asarray(rng.standard_normal((4096, 16)), jnp.float32)
    hi = lo + 1.0
    pq = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    lb, dt = common.timed(
        lambda: ops.lb_isax(pq, lo, hi, 256).block_until_ready(), repeat=3)
    est = (lo.size * 8 + lb.size * 4) / hbm_bw * 1e6
    rows.append(("kernel/lb_isax/16x4096", dt * 1e6,
                 f"v5e_est_us={est:.2f};mem_bound=True"))
    rows.extend(run_device_search())
    return rows


def run_device_search() -> list[tuple[str, float, str]]:
    """Device-resident exact search (jitted while_loop) vs host plan."""
    import numpy as np
    from repro.core.index import DumpyIndex
    from repro.core.search import exact_search
    from repro.core.search_device import exact_search_device
    db = common.dataset("rand", n=10_000)
    idx = DumpyIndex.build(db, common.params(th=256))
    qs = common.queries()[:8]
    rows = []
    t_h, t_d, vis = [], [], []
    for q in qs:
        (_, _, st), dt = common.timed(exact_search, idx, q, 10)
        t_h.append(dt * 1e6)
        (ids, d, v), dt2 = common.timed(exact_search_device, idx, q, 10)
        t_d.append(dt2 * 1e6)
        vis.append(v)
    rows.append(("device_search/host", float(np.mean(t_h)), ""))
    rows.append(("device_search/jitted", float(np.mean(t_d)),
                 f"windows_visited={np.mean(vis):.0f}"))
    return rows
