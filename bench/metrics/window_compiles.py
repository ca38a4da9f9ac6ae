"""Programs compiled or loaded from the compile cache inside the window:
the program's ``dumpy.compile`` spans that end in it."""
from bench import spans


def read(run):
    got = spans.spans(run)
    if got is None:
        return None
    t0, t1 = spans.bounds(run)
    return sum(1 for s in got[1]
               if s.name == spans.COMPILE and t0 <= s.t1 <= t1)
