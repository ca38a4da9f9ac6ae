"""Compile the main-path Pallas kernels for a TPU v5e with no chip attached.

The TPU compiler ships with ``libtpu``; ``get_topology_desc`` describes a
``v5e:2x2`` host it can target, and ``jit(...).lower(...).compile()`` then
refuses exactly what the chip would refuse (unaligned blocks, primitives
with no Mosaic lowering, kernels XLA cannot partition across chips).
Interpret-mode tests cannot see any of that.  Shapes are the deployment's:
series length n=256, w=16, a 64-query batch, ``interpret=False``.

The topology is described inside a module fixture, never at import, so
that every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

N, W, Q, BAND = 256, 16, 64, 25


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _span_scan_case(sds, kk):
    """The exact walk at the deployment's shapes: one shard of 2^22 rows
    in 2,048-row spans, a 64-query batch, ``kk`` 18 (plain) or 48 (fuzzy)."""
    from repro.kernels import span_scan
    f32, i32 = jnp.float32, jnp.int32
    S, T, C = 1, 1 << 22, 2048
    W = T // C
    return (lambda q, db, al, ids, st, ld, sz, lb, sf: span_scan.span_scan(
                q, db, al, ids, st, ld, sz, lb, sf, kk=kk, chunk=C),
            (sds((Q, N), f32), sds((S, T, N), f32), sds((S, T), jnp.bool_),
             sds((S, T), i32), sds((S, W), i32), sds((S, W), i32),
             sds((S, W), i32), sds((S, Q, W), f32), sds((S, Q, W + 1), f32)))


def _kernel_cases(sds):
    from repro.kernels import dtw_band, lb_isax, lb_keogh, sax_encode
    f32 = jnp.float32
    m = 2048
    return {
        "span_scan_kk18": _span_scan_case(sds, 18),
        "span_scan_kk48": _span_scan_case(sds, 48),
        "sax_encode": (lambda x: sax_encode.sax_encode(x, w=W, b=8),
                       (sds((Q, N), f32),)),
        "lb_paa_interval": (
            lambda a, b, lo, hi: lb_isax.lb_paa_interval(a, b, lo, hi, n=N),
            (sds((Q, W), f32), sds((Q, W), f32), sds((1200, W), f32),
             sds((1200, W), f32))),
        "dtw_band": (
            lambda q, x, mk, c: dtw_band.dtw_band(q, x, mk, c, r=BAND),
            (sds((Q, N), f32), sds((m, N), f32), sds((Q, m), jnp.bool_),
             sds((Q,), f32))),
        "lb_improved": (
            lambda x, q, u, lo: lb_keogh.lb_improved(x, q, u, lo, r=BAND),
            (sds((m, N), f32), sds((N,), f32), sds((N,), f32),
             sds((N,), f32))),
    }


@pytest.mark.parametrize("kernel", ["sax_encode", "lb_paa_interval",
                                    "dtw_band", "lb_improved",
                                    "span_scan_kk18", "span_scan_kk48"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = _kernel_cases(sds)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("program", ["exact", "extended"])
def test_sharded_search_compiles_for_four_chips(topo, monkeypatch, program):
    """The sharded search programs on a 4-chip ``data`` mesh, with the
    kernels (not their CPU twins) inside: a Mosaic kernel outside
    ``shard_map`` in a multi-chip program is refused.  The exact program's
    span walk is the ``span_scan`` kernel, one call a chip."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed as D
    from repro.kernels import ops
    # steer the wrappers onto their TPU branch: this process's backend is
    # the CPU, but the program is compiled for the described chips
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    shapes = dict(n_series=1 << 16, length=N, w=W, chunk=2048,
                  n_leaves=64, k=18, q_batch=Q)
    lower = (D.lower_search_sharded if program == "exact"
             else D.lower_search_extended)
    text = lower(mesh, **shapes).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    if program == "exact":
        assert "span_scan" in text
