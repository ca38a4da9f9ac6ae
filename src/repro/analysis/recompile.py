"""Runtime recompile guard: bounded cache-key cardinality under a sweep.

The repo's serving story hinges on programs being compiled at index load,
not per request (``bench_batch_search`` measures steady state on that
assumption, and ``DumpyIndex._n_device_builds`` already guards the
device-*state* analogue).  This module guards the device-*program* side:

* :class:`CompileCounter` counts every program obtained from the backend
  while active, compiled or loaded from the persistent cache: it reads the
  ``dumpy.compile`` records of :mod:`repro.obs`, which hears every call of
  the compile funnel both ``jit`` and ``pjit`` executables pass through
  (tracing-cache hits never reach it).
* :func:`run_sweep` drives the public batched search entry points across a
  k × nbr × metric × batch grid **twice** and reports both passes'
  counts.  The contract: pass 2 adds *zero* compiles (every static/shape
  combination was cached by pass 1), and pass 1 stays under a declared
  budget (no hidden per-call specialization, e.g. a host value leaking
  into a static argument).

``verify_sweep`` raises ``RecompileViolation`` on either breach — the
gate tests (``tests/test_analysis_recompile.py``) assert it trips when a
fresh-jit-per-call wrapper is patched in.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro import obs

#: compiles one (metric, k, batch)-combo may cost on its cold pass: the
#: entry program plus its inner jitted helpers (query prep, encode, LB
#: kernels, dedup/top-k, finalize).  The default sweep measures ~2.
COMPILES_PER_COMBO = 8


class RecompileViolation(AssertionError):
    """A jitted entry point recompiled when its cache should have hit."""


class CompileCounter:
    """Context manager counting the programs obtained from the backend
    between enter and exit (see module docstring), with their names.

    Nesting is safe and the count is per-instance; compiles on other
    threads inside the scope are counted too."""

    def __init__(self) -> None:
        self.count = 0
        self.names: list[str] = []
        self._start = self._mark = None

    def __enter__(self) -> "CompileCounter":
        self._start, self._mark = obs.compiles(), obs.mark()
        return self

    def __exit__(self, *exc) -> None:
        self.count = obs.compiles() - self._start
        self.names = [s.attrs["fun"] for s in obs.spans()
                      if s.name == obs.COMPILE and s.sid > self._mark]


@dataclass(frozen=True)
class SweepReport:
    first_pass: int
    second_pass: int
    budget: int
    combos: int
    second_pass_names: tuple[str, ...]

    @property
    def ok(self) -> bool:
        return self.second_pass == 0 and self.first_pass <= self.budget


def _default_index(n: int = 2048, length: int = 64):
    from repro.core.build import DumpyParams
    from repro.core.index import DumpyIndex
    from repro.core.sax import SaxParams
    from repro.core.split import SplitParams
    from repro.data.series import random_walks

    db = random_walks(n, length, seed=7)
    p = DumpyParams(sax=SaxParams(w=8, b=8), split=SplitParams(th=128))
    return DumpyIndex.build(db, p)


def run_sweep(index=None, *, ks=(5, 10), nbrs=(2, 4), metrics=("ed", "dtw"),
              batches=(4, 8), buckets=(1, 2, 4, 8), exact_fn=None,
              extended_fn=None, bucket_fn=None) -> SweepReport:
    """Run the k/nbr/metric/batch sweep twice and count compiles per pass.

    ``buckets`` adds the serving bucket ladder: each bucket size runs once
    per metric with a *different* per-lane k/nbr/metric mix (plus a dead
    padding lane), so a warm-pass compile proves a per-request knob leaked
    into the bucket program's cache key — the contract behind the
    coalescing front-end (docs/serving.md) is that the key is the batch
    shape plus the single metric-presence static (``has_dtw``), never a
    knob *value*.

    ``exact_fn`` / ``extended_fn`` / ``bucket_fn`` default to the public
    batched entry points; tests substitute misbehaving wrappers to prove
    the gate trips.
    """
    from repro.core import search_device as sd
    from repro.data.series import query_workload

    if index is None:
        index = _default_index()
    exact_fn = exact_fn or sd.exact_search_device_batch
    extended_fn = extended_fn or sd.extended_search_device_batch
    bucket_fn = bucket_fn or sd.bucket_search_device_batch

    length = index.db.shape[1]
    qs = query_workload(max((*batches, *buckets), default=8), length)
    k_hi, nbr_hi = max(ks), max(nbrs)

    def one_pass(counter: CompileCounter) -> None:
        with counter:
            for met in metrics:
                for k in ks:
                    for b in batches:
                        exact_fn(index, qs[:b], k, metric=met)
                for nbr in nbrs:
                    extended_fn(index, qs[: max(batches)], max(ks), nbr=nbr,
                                metric=met)
            for j, met in enumerate(metrics):
                for B in buckets:
                    # rotate the lane mix with j so the two metric rounds
                    # hand the *same program* different traced knob values
                    lane_k = [ks[(i + j) % len(ks)] for i in range(B)]
                    lane_nbr = [nbrs[(i + j) % len(nbrs)] for i in range(B)]
                    lane_m = [metrics[(i + j) % len(metrics)]
                              for i in range(B)]
                    lane_m[0] = met
                    if B > 1:
                        lane_k[-1] = 0          # one dead padding lane
                    bucket_fn(index, qs[:B], lane_k, lane_nbr, lane_m,
                              k_max=k_hi, nbr_max=nbr_hi)

    index.device_index()                # device state builds outside the count
    first, second = CompileCounter(), CompileCounter()
    one_pass(first)
    one_pass(second)
    combos = len(metrics) * (len(ks) * len(batches) + len(nbrs)) \
        + 2 * len(buckets)     # per bucket shape: pure-ED + mixed variants
    return SweepReport(first_pass=first.count, second_pass=second.count,
                       budget=combos * COMPILES_PER_COMBO, combos=combos,
                       second_pass_names=tuple(second.names))


def verify_sweep(report: SweepReport | None = None, **kw) -> SweepReport:
    """Raise :class:`RecompileViolation` unless the sweep is steady-state."""
    rep = report if report is not None else run_sweep(**kw)
    if rep.second_pass != 0:
        names = ", ".join(rep.second_pass_names[:8])
        raise RecompileViolation(
            f"{rep.second_pass} recompile(s) on the warm pass of the "
            f"k/nbr/metric/batch sweep (programs: {names}) — a cache key is "
            f"unstable (unhashable static? host value in the key?)")
    if rep.first_pass > rep.budget:
        raise RecompileViolation(
            f"cold pass compiled {rep.first_pass} programs for "
            f"{rep.combos} static combos (budget {rep.budget}) — per-call "
            f"specialization is leaking into the jit cache key")
    return rep
