"""Device build, stage 1 (SAX encoding of the collection): the program's
``dumpy.build.encode`` span in set-up."""
from bench import spans


def read(run):
    return spans.setup_s(run, "dumpy.build.encode")
