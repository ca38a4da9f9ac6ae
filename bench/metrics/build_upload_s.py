"""The resident ``DeviceIndex`` assembled and uploaded from the built
index: the program's ``dumpy.device_index`` span in set-up."""
from bench import spans


def read(run):
    return spans.setup_s(run, "dumpy.device_index")
