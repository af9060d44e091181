"""A share of a roofline, in %, for what a latent-attention, routed-expert
decoder adds (``benchmark/rooflines_moe.py``), over the traced slice: the
least time the chip could take for the work each dispatch was handed (the
larger of operations over peak and bytes over HBM bandwidth), over the
device time of the ops named by ``kernels``. ``work`` is ``experts`` (the
work is read from the ``expert_rows`` and ``experts_hit`` attributes of
the program's ``span`` events inside the ``within`` slice) or ``latent``
(from the dispatch sizes the runner kept). Nothing where the trace shows
none of those ops, the run kept no sizes, or the program has no such span
(a program older than the counters)."""
from benchmark import program_spans, rooflines, rooflines_moe
from benchmark.readers.kernel_ms import kernel_seconds


def read(run, kernels, work, span=None, within=None):
    total = kernel_seconds(run, kernels)
    if total is None:
        return None
    m = run["config"]
    if work == "experts":
        handed = [(e["stats"]["expert_rows"], e["stats"]["experts_hit"])
                  for e in program_spans.sliced(within)
                  if e["name"] == span and "expert_rows" in e["stats"]
                  and "experts_hit" in e["stats"]]
        works = [rooflines_moe.expert_work(m, *h) for h in handed]
    else:
        works = [rooflines_moe.latent_work(m, *s)
                 for s in run["samples"].get("slice_sizes") or ()]
    if not works:
        return None
    least = sum(rooflines.roofline_seconds(f, b, run["peak"])
                for f, b in works)
    return 100.0 * least / total
