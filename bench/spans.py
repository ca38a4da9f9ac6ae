"""The program's own spans and counters (``repro.obs``), as the per-layer
readers take them: on the clock of the run's window (``time.perf_counter``,
which ``repro.obs`` reads in ns), split into set-up (spans that lie between
the run's start and the window's) and the window itself.

A program without ``repro.obs`` gives nothing: every function returns
``None`` and raises nothing, so a reader leaves its metric out.
"""
from __future__ import annotations

CALL = "dumpy.exact.call"
COMPILE = "dumpy.compile"


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def bounds(run) -> tuple[int, int]:
    """The window ``[t0, t1]`` in ``perf_counter_ns``."""
    ctx = run["ctx"]
    t0 = ctx.t_start + ctx.setup_s
    return round(t0 * 1e9), round((t0 + ctx.window_s) * 1e9)


def spans(run):
    """``(obs, every span in the ring)``, or ``None``."""
    obs = _obs()
    return None if obs is None else (obs, obs.spans())


def per_call_self_ms(run, name: str) -> float | None:
    """Self time of span ``name`` over the exact calls that start in the
    window, in ms a call."""
    got = spans(run)
    if got is None:
        return None
    obs, sp = got
    win = obs.clip(sp, *bounds(run))
    calls = [s for s in win if s.name == CALL]
    own = obs.self_time(win, name)
    if not calls or not own:
        return None
    return sum(own) / len(calls) * 1e-6


def setup_s(run, name: str) -> float | None:
    """Seconds of the spans named ``name`` in the run's set-up: from its
    start to the window's."""
    got = spans(run)
    if got is None:
        return None
    obs, sp = got
    d = [s.t1 - s.t0 for s in obs.clip(sp, round(run["ctx"].t_start * 1e9),
                                       bounds(run)[0])
         if s.name == name]
    return sum(d) * 1e-9 if d else None


def window_counts(run):
    """``(calls, {call sid: {counter: n}})`` for the exact calls that start
    in the window, or ``None`` when they recorded no counter."""
    got = spans(run)
    if got is None:
        return None
    obs, sp = got
    t0, t1 = bounds(run)
    calls = [s for s in obs.clip(sp, t0, t1) if s.name == CALL]
    by_call = {c.sid: {} for c in calls}
    for c in obs.clip(obs.counters(), t0, t1):
        if c.span in by_call:
            by_call[c.span][c.name] = by_call[c.span].get(c.name, 0) + c.n
    if not any(by_call.values()):
        return None
    return calls, by_call
