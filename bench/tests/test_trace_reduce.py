"""The trace reduction, on hand-made intervals and on a trace recorded
here on the CPU."""
import time

import pytest

from bench import trace_reduce as tr


def test_reduce_hand_made():
    ms = 1e6
    spans = [("bench.window", 0, 100 * ms), ("bench.exact_call", 0, 40 * ms),
             ("bench.gen_sleep", 60 * ms, 100 * ms)]
    ops = [[("while", 10 * ms, 35 * ms), ("fusion.1", 12 * ms, 20 * ms),
            ("fusion.1", 22 * ms, 32 * ms), ("sort", 70 * ms, 75 * ms),
            ("outside", 150 * ms, 160 * ms)]]
    s = tr.reduce(ops, spans)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.030)      # [10, 35] and [70, 75]
    assert s["idle_share"] == pytest.approx(0.7)
    # self time: the while holds the two fusions
    assert s["device_ops"] == [["fusion.1", pytest.approx(0.018)],
                               ["while", pytest.approx(0.007)],
                               ["sort", pytest.approx(0.005)]]
    gaps = s["idle_gaps"]
    # [35, 70] overlaps the call by 5 ms and the sleep by 10 ms
    assert [g[0] for g in gaps] == ["bench.gen_sleep", "bench.gen_sleep",
                                    "bench.exact_call"]
    assert [g[1] for g in gaps] == pytest.approx([0.035, 0.025, 0.010])


def test_reduce_averages_devices_and_needs_one_window():
    ms = 1e6
    spans = [("bench.window", 0, 10 * ms)]
    s = tr.reduce([[("a", 0, 10 * ms)], [("a", 0, 5 * ms)]], spans)
    assert s["busy_s"] == pytest.approx(0.0075)
    with pytest.raises(ValueError):
        tr.reduce([[]], [])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.exact_call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.gen_sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    s = tr.reduce_dir(str(tmp_path))
    assert 0.06 <= s["window_s"] < 5.0
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    assert any(name == "bench.gen_sleep" for name, _ in s["idle_gaps"])
    assert len(s["idle_gaps"]) <= tr.TOP
