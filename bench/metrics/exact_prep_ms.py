"""Host query preparation of an exact call (validation, upload, encoding):
self time of the program's ``dumpy.exact.prep`` span, per call."""
from bench import spans


def read(run):
    return spans.per_call_self_ms(run, "dumpy.exact.prep")
