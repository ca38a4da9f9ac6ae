"""Share of the exact window in which no operation ran on the device
(1 - busy / window, from the trace)."""


def read(run):
    t = run["trace"]
    if "exact_calls" not in run["counters"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
