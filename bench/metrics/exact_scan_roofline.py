"""The exact span loop's share of its roofline: the least time the chip
needs for the spans the window's calls must read and score
(``bench.counts.exact_work``) over the device's busy time."""
from bench import counts


def read(run):
    c = run["counters"]
    if "spans_visited" not in c:
        return None
    nbytes, flops = counts.exact_work(c["spans_visited"], c["span_rows"],
                                      c["n"])
    return counts.roofline_pct(nbytes, flops, run["trace"]["busy_s"],
                               counts.peaks(run["device"]["kind"]))
