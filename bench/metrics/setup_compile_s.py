"""Seconds of set-up spent obtaining programs from the backend (compiling,
or loading from the compile cache): the ``dumpy.compile`` spans that end
before the window."""
from bench import spans


def read(run):
    return spans.setup_s(run, spans.COMPILE)
