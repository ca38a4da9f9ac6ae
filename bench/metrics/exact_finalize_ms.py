"""Host re-rank of an exact call's candidates (``_finalize_exact``): self
time of the program's ``dumpy.exact.finalize`` span, per call."""
from bench import spans


def read(run):
    return spans.per_call_self_ms(run, "dumpy.exact.finalize")
