"""A percentile of every sample of a series (``q`` = 50 is the median)."""
from benchmark import stats


def read(run, series, q):
    return stats.percentile(run["samples"].get(series) or [], q)
