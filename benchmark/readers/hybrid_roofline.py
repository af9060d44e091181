"""A share of a roofline, in %, for what a decoder-hybrid-decoder adds
(``benchmark/rooflines_hybrid.py``), over the traced slice: the least
time the chip could take for the work each dispatch was handed, over the
device time of the ops named by ``kernels``. ``work`` is ``scan`` (bytes
at the HBM peak) or ``attention`` (the larger of operations and bytes at
their peaks). Nothing where the trace shows none of those ops, or the
run kept no dispatch sizes."""
from benchmark import rooflines, rooflines_hybrid
from benchmark.readers.kernel_ms import kernel_seconds


def read(run, kernels, work):
    total = kernel_seconds(run, kernels)
    sizes = run["samples"].get("slice_sizes")
    if total is None or not sizes:
        return None
    m = run["config"]
    if work == "scan":
        least = sum(rooflines_hybrid.scan_bytes(m, *s) for s in sizes) \
            / run["peak"]["hbm_bytes_per_s"]
    else:
        least = sum(rooflines.roofline_seconds(
            *rooflines_hybrid.attention_work(m, *s), run["peak"])
            for s in sizes)
    return 100.0 * least / total
