"""The MEDIAN device busy time, in ms, of the traced slice's engine steps
of one ``kind``: ``decode`` (the dispatch held no prefill row) or ``chunk``
(it held one). A step's device time is what runs between its
``ptpu:engine.dispatch`` start and the next one's (host and device in
series); the kind is the span's own ``prefill_rows``. A median by kind
does not move when the slice's mix of kinds does. Nothing where the slice
holds no step of the kind or the program publishes no map."""
from benchmark import device_regions, stats


def read(run, kind, within, program):
    loaded = device_regions.for_run(run, within, program)
    if loaded is None:
        return None
    return stats.percentile(
        device_regions.by_kind(loaded["steps"]).get(kind, []), 50)
