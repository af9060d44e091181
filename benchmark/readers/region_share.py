"""The share (%) of the device's busy time in the traced slice that the
program files under the named ``regions`` (``unscoped``: under none).
Nothing where the program publishes no map."""
from benchmark import device_regions
from benchmark.readers.region_ms import region_ns


def read(run, regions, within, program):
    loaded = device_regions.for_run(run, within, program)
    if loaded is None or not loaded["busy_ns"]:
        return None
    return 100.0 * region_ns(loaded, regions) / loaded["busy_ns"]
