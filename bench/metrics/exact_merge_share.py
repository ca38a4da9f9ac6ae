"""Share of the walked spans at which the top-k merge had work: some
candidate lay below its query's running k-th best
(``exact.spans_merged``), over the spans walked (``exact.spans_walked``),
over the window's exact calls. A program that does not count merges reads
nothing."""
from bench import spans


def read(run):
    got = spans.window_counts(run)
    if got is None:
        return None
    calls, by_call = got
    merged = walked = 0
    for c in calls:
        n = by_call[c.sid]
        if "exact.spans_merged" not in n:
            return None
        merged += n["exact.spans_merged"]
        walked += n.get("exact.spans_walked", 0)
    return 100.0 * merged / walked if walked else None
