"""The span scan's share of its roofline on a mesh: the least time the
chips need for the spans the window's calls must read and score
(``bench.counts.exact_work``; ``spans_visited`` is summed over the shards
and ``span_rows`` holds every shard's spans) over the chips' summed busy
time (the trace's per-chip mean ``busy_s`` times the chip count), at one
chip's peaks."""
from bench import counts


def read(run):
    c = run["counters"]
    if "spans_visited" not in c:
        return None
    nbytes, flops = counts.exact_work(c["spans_visited"], c["span_rows"],
                                      c["n"])
    busy = run["trace"]["busy_s"] * run["device"]["count"]
    return counts.roofline_pct(nbytes, flops, busy,
                               counts.peaks(run["device"]["kind"]))
