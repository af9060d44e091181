"""The median of one attribute of the engine's ``engine.dispatch`` spans
over the traced slice, as a share (%) of a number in the workload file
(``of`` is a dotted path: ``engine.max_batched_tokens``). The attribute is
the program's own count of what each dispatch was handed. Printed on an
earlier line, beside the harness's outside copy of the same count where it
has one (``samples["slice_sizes"]``: the ``cu_seqlens`` the ``StepSpy``
copied, of which ``cu[num_seqs]`` is the dispatch's query tokens)."""
from benchmark import program_spans, stats


def read(run, span, within, stat, of):
    if not run["trace"]:
        return None
    values = program_spans.stat_values(program_spans.sliced(within), span,
                                       stat)
    if not values:
        return None
    facts = f"[spans] dispatches={len(values)} {stat}={values}"
    sizes = run["samples"].get("slice_sizes")
    if stat == "q_tokens" and sizes is not None:
        facts += f" spy_{stat}={[int(cu[int(n)]) for cu, _, n in sizes]}"
    print(facts, flush=True)
    whole = run["workload"]
    for key in of.split("."):
        whole = whole[key]
    return 100.0 * stats.percentile(values, 50) / whole
