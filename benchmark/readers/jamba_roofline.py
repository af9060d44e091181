"""A share of a roofline, in %, for what a Jamba step adds to a dense
decoder's (``benchmark/rooflines_jamba.py``), over the traced slice: the
least time the chip could take for the work each dispatch was handed,
over the device time that ran it. ``work`` ``scan``: the Mamba layers'
least bytes at the HBM peak over the device SELF time of ``regions`` (the
scan and its convolution, loops included). ``work`` ``attention``: the
larger of operations and bytes of the attention layers at their peaks,
over the device time of the ``kernels``' custom calls. Nothing where the
trace shows none of that time, or the run kept no dispatch sizes."""
from benchmark import device_regions, rooflines, rooflines_jamba
from benchmark.readers.kernel_ms import kernel_seconds
from benchmark.readers.region_ms import region_ns


def read(run, work, kernels=(), regions=(), within=None, program=None):
    sizes = run["samples"].get("slice_sizes")
    if not sizes:
        return None
    m = run["config"]
    if work == "scan":
        loaded = device_regions.for_run(run, within, program)
        spent = loaded and region_ns(loaded, regions) / 1e9
        least = sum(rooflines_jamba.scan_bytes(m, *s) for s in sizes) \
            / run["peak"]["hbm_bytes_per_s"]
    else:
        spent = kernel_seconds(run, kernels)
        least = sum(rooflines.roofline_seconds(
            *rooflines_jamba.attention_work(m, *s), run["peak"])
            for s in sizes)
    return 100.0 * least / spent if spent else None
