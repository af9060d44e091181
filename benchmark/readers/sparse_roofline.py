"""A share of a roofline, in %, for what a sparse-indexed, windowed latent
decoder adds (``benchmark/rooflines_sparse.py``), over the traced slice:
the least time the chip could take for the work each dispatch was handed
(the larger of operations over peak and bytes over HBM bandwidth), over
the device time of the ops named by ``kernels``. ``work`` is ``index``,
``sparse`` (both read the program's own ``index_*`` counts from its
``span`` events inside the ``within`` slice, one a dispatch, beside the
dispatch sizes the runner kept) or ``window`` (the dispatch sizes alone).
Nothing where the trace shows none of those ops, the run kept no sizes,
or the program has no such counters (a program older than them)."""
from benchmark import program_spans, rooflines, rooflines_sparse
from benchmark.readers.kernel_ms import kernel_seconds

COUNTERS = ("index_visible", "index_selected", "index_union")


def read(run, kernels, work, span=None, within=None):
    total = kernel_seconds(run, kernels)
    sizes = run["samples"].get("slice_sizes")
    if total is None or not sizes:
        return None
    m = run["config"]
    if work == "window":
        works = [rooflines_sparse.window_work(m, *s) for s in sizes]
    else:
        counted = [e["stats"] for e in program_spans.sliced(within)
                   if e["name"] == span
                   and all(k in e["stats"] for k in COUNTERS)]
        # one post span a dispatch, in order; a slice cut mid-step keeps
        # the pairs that are whole
        pairs = list(zip(counted, sizes))
        if not pairs:
            return None
        if work == "index":
            works = [rooflines_sparse.index_work(m, c["index_visible"], *s)
                     for c, s in pairs]
        else:
            works = [rooflines_sparse.sparse_work(
                m, c["index_selected"], c["index_union"], *s)
                for c, s in pairs]
    least = sum(rooflines.roofline_seconds(f, b, run["peak"])
                for f, b in works)
    return 100.0 * least / total
