"""A share of a roofline, in %, for the linear-attention layers of an
Olmo Hybrid step (``benchmark/rooflines_olmo_hybrid.py``), over the
traced slice: the least time the chip could take for what each dispatch
was handed (the larger of the delta rule's operations at the MXU peak
and its least bytes at the HBM peak), over the device SELF time of
``regions`` (the convolution and the recurrence, loops included:
``serve.gdn_ms``'s time). Nothing where the program publishes no map,
the trace shows none of that time, or the run kept no dispatch sizes."""
from benchmark import device_regions, rooflines, rooflines_olmo_hybrid
from benchmark.readers.region_ms import region_ns


def read(run, regions, within, program):
    sizes = run["samples"].get("slice_sizes")
    if not sizes:
        return None
    loaded = device_regions.for_run(run, within, program)
    spent = loaded and region_ns(loaded, regions) / 1e9
    if not spent:
        return None
    least = sum(rooflines.roofline_seconds(
        *rooflines_olmo_hybrid.delta_work(run["config"], *s), run["peak"])
        for s in sizes)
    return 100.0 * least / spent
