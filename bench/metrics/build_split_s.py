"""Device build, stages 2-4 (grouping by SAX word, the adaptive split plan,
sibling packing, fuzzy duplication): the program's ``dumpy.build.split``
span in set-up."""
from bench import spans


def read(run):
    return spans.setup_s(run, "dumpy.build.split")
