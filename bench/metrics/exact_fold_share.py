"""Share of the merged spans (``exact.spans_merged``) at which no query held
two candidates below its running k-th best in one 128-column lane
(``exact.spans_collided``), over the window's exact calls: the spans whose
top-k merge runs every round on the span scan's lane fold, which only a
lane holding a second candidate can make it rebuild. A program that does
not count lane collisions reads nothing."""
from bench import spans


def read(run):
    got = spans.window_counts(run)
    if got is None:
        return None
    calls, by_call = got
    collided = merged = 0
    for c in calls:
        n = by_call[c.sid]
        if "exact.spans_collided" not in n:
            return None
        collided += n["exact.spans_collided"]
        merged += n.get("exact.spans_merged", 0)
    return 100.0 * (1 - collided / merged) if merged else None
