"""Device SELF time per iteration of the traced slice, in ms, of the ops
the program files under the named ``regions`` (``benchmark/device_regions``:
the program's own instruction -> region map, a ``while``'s own time with
its region, nothing counted twice). ``regions`` "*" is every named region;
``but`` takes some out again; ``direction`` keeps ``forward`` or
``backward`` ops only (a train step). Nothing where the program publishes
no map (the parent of the PR that added it) or the slice shows no op."""
from benchmark import device_regions


def region_ns(loaded, regions, but=(), direction=None):
    total = 0.0
    for (region, backward), ns in loaded["by"].items():
        if region in but or (direction is not None
                             and backward != (direction == "backward")):
            continue
        if region in regions or (regions == "*"
                                 and region != device_regions.UNSCOPED):
            total += ns
    return total


def read(run, regions, within, program, but=(), direction=None):
    loaded = device_regions.for_run(run, within, program)
    if loaded is None:
        return None
    return (region_ns(loaded, regions, tuple(but), direction)
            / run["trace"]["iterations"] / 1e6)
