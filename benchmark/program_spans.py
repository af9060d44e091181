"""The program's own spans, read back from the run's profiler trace.

``paddle_tpu.profiler.RecordEvent`` writes every span of the program
into the profiler's trace as ``ptpu:<name>`` (attributes as the event's
typed stats), on the clock of the device's ``XLA Ops`` and of the
harness's ``bench:<name>`` spans (``trace.py``). This module finds the
run's trace the way ``trace.newest_xplane`` does, parses it once per
process, and cuts the program's events to the traced slice: from the
start of the first to the end of the last ``bench:<within>`` span, whole
harness iterations only. A program without such spans (the parent of the
PR that added them) yields nothing, and every reader here then reads
None. The arithmetic is pure and checked on hand-made events in
``tests/test_program_spans.py``.
"""
from __future__ import annotations

import functools
import os

from benchmark import stats, trace

PREFIX = "ptpu:"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_out", "trace")


# --------------------------------------------------------------------------
# arithmetic on events: dicts of name, start, end (ns), line, stats
# --------------------------------------------------------------------------
def slice_bounds(harness, within):
    """(start of the first, end of the last) harness span called
    ``within``; None where the trace holds none."""
    marks = [(a, b) for name, a, b in harness if name == within]
    if not marks:
        return None
    return min(a for a, _ in marks), max(b for _, b in marks)


def in_slice(events, bounds):
    """The events that lie wholly inside ``bounds``, in start order."""
    t0, t1 = bounds
    return sorted((e for e in events if t0 <= e["start"] and e["end"] <= t1),
                  key=lambda e: (e["start"], -e["end"]))


def contained(outer, events, names):
    """The events called one of ``names`` that ``outer`` contains: on
    its thread line, inside its interval."""
    return [e for e in events if e is not outer and e["name"] in names
            and e["line"] == outer["line"]
            and outer["start"] <= e["start"] and e["end"] <= outer["end"]]


def self_ns(outer, events, minus):
    """``outer``'s duration minus the summed durations of the ``minus``
    spans it contains (same thread line; siblings do not overlap)."""
    return (outer["end"] - outer["start"]) - sum(
        e["end"] - e["start"] for e in contained(outer, events, minus))


def durations_ms(events, span, minus=()):
    """For every ``span`` event among ``events``: its duration, less the
    ``minus`` spans inside it, in ms."""
    return [self_ns(e, events, minus) / 1e6 for e in events
            if e["name"] == span]


def stat_values(events, span, stat):
    """The ``stat`` attribute of every ``span`` event that has it."""
    return [e["stats"][stat] for e in events
            if e["name"] == span and stat in e["stats"]]


# --------------------------------------------------------------------------
# reading the profiler's file
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def parse(path):
    """``{"program": [event], "harness": [(name, start, end)]}`` of one
    ``.xplane.pb``: the host planes' ``ptpu:`` and ``bench:`` events.
    A thread line is named by its plane and its place in it (two
    threads' lines may share a name)."""
    from jax.profiler import ProfileData

    program, harness = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name.startswith(PREFIX):
                    program.append({
                        "name": ev.name[len(PREFIX):], "start": ev.start_ns,
                        "end": end, "line": (plane.name, i),
                        "stats": dict(ev.stats)})
                elif ev.name.startswith(trace.PREFIX):
                    harness.append((ev.name[len(trace.PREFIX):],
                                    ev.start_ns, end))
    return {"program": program, "harness": harness}


def sliced(within):
    """The program's events inside the newest trace's ``within`` slice;
    [] where there is no trace, no such harness span, or no program
    span (a program older than its spans)."""
    try:
        parsed = parse(trace.newest_xplane(TRACE_ROOT))
    except FileNotFoundError:
        return []
    bounds = slice_bounds(parsed["harness"], within)
    if bounds is None:
        return []
    return in_slice(parsed["program"], bounds)


def percentile_ms(within, span, q=50, minus=()):
    """The ``q``-th percentile over the slice of ``span``'s durations
    (less the ``minus`` spans inside each), in ms; None where none."""
    return stats.percentile(durations_ms(sliced(within), span, minus), q)
