"""Index build: ``DumpyIndex.build(backend="device")`` and the resident
``DeviceIndex``, on the host clock, ending in ``block_until_ready``."""


def read(run):
    return run["ctx"].timers.get("build_s")
