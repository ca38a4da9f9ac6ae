"""Share of the span loop's scoring that was needed: live rows × active
queries (``exact.pairs_needed``) over the slab rows × queries it scored
(``exact.spans_walked`` × the call's ``chunk`` × ``Q``), over the window's
exact calls."""
from bench import spans


def read(run):
    got = spans.window_counts(run)
    if got is None:
        return None
    calls, by_call = got
    need = scored = 0
    for c in calls:
        n = by_call[c.sid]
        need += n.get("exact.pairs_needed", 0)
        scored += n.get("exact.spans_walked", 0) * c.attrs["chunk"] \
            * c.attrs["Q"]
    return 100.0 * need / scored if scored else None
