"""Device time per exact call: device busy time in the traced window over
the ``exact_search_device_batch`` calls made in it."""


def read(run):
    n = run["counters"].get("exact_calls")
    busy = run["trace"]["busy_s"]
    if not n or busy <= 0:
        return None
    return busy / n * 1e3
