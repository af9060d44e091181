"""The median, over the ``span`` events of the traced slice, of one
attribute over another (``num`` / ``den``): two of the program's own
counts of one dispatch. Nothing where the slice holds no such span or
the span lacks either attribute (a program older than the counters)."""
from benchmark import program_spans, stats


def read(run, span, within, num, den):
    if not run["trace"]:
        return None
    ratios = [e["stats"][num] / e["stats"][den]
              for e in program_spans.sliced(within)
              if e["name"] == span and num in e["stats"]
              and e["stats"].get(den)]
    return stats.percentile(ratios, 50)
