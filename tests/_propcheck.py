"""Property-testing compatibility shim (offline-friendly hypothesis).

The test suite property-tests the iSAX invariants with hypothesis when it is
installed (it is a ``dev`` extra in ``pyproject.toml``).  Where it is not,
this module degrades ``@given`` / ``strategies`` / ``hypothesis.extra.numpy``
to deterministic seeded-numpy example sampling with the same call surface:

    from _propcheck import given, settings, st, hnp

* With real hypothesis available, the genuine objects are re-exported and
  nothing changes.
* Without it, ``@given(...)`` runs the test once per sampled example
  (``max_examples`` from the paired ``@settings``, default 20).  Sampling is
  seeded per-test (crc32 of the test name), so failures reproduce exactly.
  Scalar integer strategies probe both range endpoints before sampling
  uniformly — a cheap stand-in for hypothesis's boundary shrinking.

Only the strategy surface the suite actually uses is implemented:
``st.integers``, ``st.floats``, ``hnp.arrays`` and ``Strategy.map``.
"""
from __future__ import annotations

import functools
import zlib

import numpy as np

try:  # pragma: no cover - exercised only where hypothesis is installed
    from hypothesis import given, settings, strategies as st
    import hypothesis.extra.numpy as hnp
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class Strategy:
        """Minimal strategy: a sampler plus optional boundary examples."""

        def __init__(self, sample_fn, boundary=()):
            self._sample = sample_fn
            self.boundary = tuple(boundary)

        def sample(self, rng: np.random.Generator):
            return self._sample(rng)

        def map(self, fn):
            return Strategy(lambda rng: fn(self._sample(rng)),
                            boundary=[fn(b) for b in self.boundary])

    class _Integers:
        @staticmethod
        def integers(lo: int, hi: int) -> Strategy:
            return Strategy(lambda rng: int(rng.integers(lo, hi + 1)),
                            boundary=(lo, hi))

        @staticmethod
        def floats(lo: float, hi: float, width: int = 64) -> Strategy:
            dt = np.float32 if width == 32 else np.float64
            return Strategy(lambda rng: dt(rng.uniform(lo, hi)))

    class _Arrays:
        @staticmethod
        def arrays(dtype, shape, elements: Strategy | None = None) -> Strategy:
            shape = (shape,) if isinstance(shape, int) else tuple(shape)

            def sample(rng: np.random.Generator):
                if elements is None:
                    return rng.standard_normal(shape).astype(dtype)
                flat = [elements.sample(rng) for _ in range(
                    int(np.prod(shape)) if shape else 1)]
                return np.asarray(flat, dtype=dtype).reshape(shape)

            return Strategy(sample)

    st = _Integers()
    hnp = _Arrays()

    def settings(*, max_examples: int = 20, deadline=None, **_ignored):
        def deco(fn):
            fn._propcheck_max_examples = max_examples
            return fn
        return deco

    def given(*strategies: Strategy):
        def deco(fn):
            import inspect
            n_examples = getattr(fn, "_propcheck_max_examples", 20)
            seed = zlib.crc32(fn.__qualname__.encode())
            sig = inspect.signature(fn)
            params = list(sig.parameters)
            # strategies bind to the RIGHTMOST parameters (hypothesis
            # semantics); earlier parameters stay pytest fixtures
            ex_names = params[len(params) - len(strategies):]

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                rng = np.random.default_rng(seed)
                # endpoint probes first (shared index across strategies keeps
                # the example count at max_examples, like hypothesis's budget)
                n_boundary = max((len(s.boundary) for s in strategies),
                                 default=0)
                for i in range(min(n_boundary, n_examples)):
                    ex = [s.boundary[i] if i < len(s.boundary)
                          else s.sample(rng) for s in strategies]
                    fn(*args, **kwargs, **dict(zip(ex_names, ex)))
                for _ in range(max(n_examples - n_boundary, 0)):
                    ex = [s.sample(rng) for s in strategies]
                    fn(*args, **kwargs, **dict(zip(ex_names, ex)))

            # pytest must not inject fixtures for the strategy-bound params
            wrapper.__signature__ = sig.replace(parameters=[
                p for name, p in sig.parameters.items()
                if name not in ex_names])
            return wrapper
        return deco
