"""100 x the sum of one attribute over the sum of another, over the
``span`` events of the traced slice that carry both: two of the program's
own counters, summed before they are divided (a share of tokens, not a
median of shares). Nothing where the slice holds no such span or the span
lacks either attribute (a program older than the counters)."""
from benchmark import program_spans


def read(run, span, within, num, den):
    if not run["trace"]:
        return None
    pairs = [(e["stats"][num], e["stats"][den])
             for e in program_spans.sliced(within)
             if e["name"] == span and num in e["stats"]
             and den in e["stats"]]
    whole = sum(d for _, d in pairs)
    if not whole:
        return None
    print(f"[spans] {span}: {len(pairs)} events, {num}="
          f"{sum(n for n, _ in pairs)} of {den}={whole}", flush=True)
    return 100.0 * sum(n for n, _ in pairs) / whole
