"""A percentile, in ms, of one of the program's own spans over the traced
slice: the span's duration minus the summed durations of the ``minus``
spans it contains (``q`` = 50 is the median; ``within`` names the harness
span whose first start and last end cut the slice). Nothing where the
slice holds no such span: a program older than its spans."""
from benchmark import program_spans


def read(run, span, within, q=50, minus=()):
    if not run["trace"]:
        return None
    return program_spans.percentile_ms(within, span, q, tuple(minus))
