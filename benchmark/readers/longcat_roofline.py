"""A share of a roofline, in %, for the LongCat-Flash decoder's expert
layers or latent calls (``benchmark/rooflines_longcat.py``), over the
traced slice: the least time the chip could take for the work each
dispatch was handed (the larger of operations over peak and bytes over
HBM bandwidth), over the device time of the ops named by ``kernels``.
``work`` is ``experts`` (read from the ``expert_rows`` and ``experts_hit``
attributes of the program's ``span`` events inside the ``within`` slice:
assignments to HELD routed experts, so identity picks count nowhere) or
``latent`` (from the dispatch sizes the runner kept, two calls a layer).
Nothing where the trace shows none of those ops, the run kept no sizes,
or the program has no such span."""
from benchmark import program_spans, rooflines, rooflines_longcat
from benchmark.readers.kernel_ms import kernel_seconds


def read(run, kernels, work, span=None, within=None):
    total = kernel_seconds(run, kernels)
    if total is None:
        return None
    m = run["config"]
    if work == "experts":
        works = [rooflines_longcat.expert_work(
            m, e["stats"]["expert_rows"], e["stats"]["experts_hit"])
            for e in program_spans.sliced(within)
            if e["name"] == span and "expert_rows" in e["stats"]
            and "experts_hit" in e["stats"]]
    else:
        works = [rooflines_longcat.latent_work(m, *s)
                 for s in run["samples"].get("slice_sizes") or ()]
    if not works:
        return None
    least = sum(rooflines.roofline_seconds(f, b, run["peak"])
                for f, b in works)
    return 100.0 * least / total
