"""Device build, stage 5 (the leaf-contiguous rows, ordered on the device):
the program's ``dumpy.build.layout`` span in set-up."""
from bench import spans


def read(run):
    return spans.setup_s(run, "dumpy.build.layout")
