"""How evenly the shards of a mesh worked: the spans walked over all
shards (``exact.spans_walked``) over the call's ``shards`` times the most
one shard walked (``exact.spans_walked_max``), over the window's exact
calls.  At 100% no chip waits on a slower shard before the merge.  A
program without that counter, or without the call's ``shards``, reads
nothing."""
from bench import spans


def read(run):
    got = spans.window_counts(run)
    if got is None:
        return None
    calls, by_call = got
    walked = most = 0
    for c in calls:
        n = by_call[c.sid]
        shards = (c.attrs or {}).get("shards")
        if "exact.spans_walked_max" not in n or not shards:
            return None
        walked += n.get("exact.spans_walked", 0)
        most += shards * n["exact.spans_walked_max"]
    return 100.0 * walked / most if most else None
