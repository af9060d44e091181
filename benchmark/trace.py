"""Harness spans, and the reduction from a profiler trace to numbers.

Spans are recorded by the benchmark around its calls into the program
(``--trace 1`` only): each is kept in memory on the host clock and also
written into the profiler's own trace as ``bench:<name>``, so that an
idle gap of the device can be named by what the host was doing on the
same clock. The reduction reads the device planes' ``XLA Ops`` lines
through ``jax.profiler.ProfileData``: busy time is the union of the
intervals in which an operation ran; idle is the window minus busy.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time

PREFIX = "bench:"
SMALL_GAP_NS = 20_000   # gaps shorter than this are summed, not named


class Spans:
    """In-memory spans on ``time.perf_counter`` plus a TraceAnnotation
    of the same name. ``open``/``close`` exist because a span may start
    in one call frame and end in another."""

    def __init__(self):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.records = []       # (name, t0, t1), host clock, seconds
        self._open = {}

    def open(self, name):
        ann = self._annotate(PREFIX + name)
        ann.__enter__()
        self._open[name] = (ann, time.perf_counter())

    def close(self, name):
        ann, t0 = self._open.pop(name)
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        self.records.append((name, t0, t1))
        return t1 - t0

    def is_open(self, name):
        return name in self._open

    def __call__(self, name):
        return _Span(self, name)


class _Span:
    def __init__(self, spans, name):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.spans.open(self.name)

    def __exit__(self, *exc):
        self.spans.close(self.name)


class NoSpans:
    """``--trace 0``: no span is recorded and no annotation written."""

    records = ()

    def open(self, name):
        pass

    def close(self, name):
        return 0.0

    def __call__(self, name):
        return self

    def __enter__(self):
        pass

    def __exit__(self, *exc):
        pass


def start(trace_dir):
    """Start the profiler with the Python tracer off (it floods the
    trace and slows the host); host annotations stay on."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


# --------------------------------------------------------------------------
# interval arithmetic (pure; checked in tests/test_benchmark.py)
# --------------------------------------------------------------------------
def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint
    (start, end); touching and overlapping intervals fuse."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def busy(intervals, t0, t1):
    """Seconds (or whatever unit the stamps have) inside [t0, t1] in
    which at least one interval is open."""
    return sum(b - a for a, b in merge(clip(intervals, t0, t1)))


def idle_gaps(intervals, t0, t1):
    """The disjoint gaps of [t0, t1] that no interval covers."""
    gaps, at = [], t0
    for a, b in merge(clip(intervals, t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def name_gaps(gaps, spans, small=0):
    """Total idle time by the innermost host span open at the time.
    ``spans`` are (name, start, end); a stretch of a gap under several
    nested spans goes to the shortest of them, one under none to
    ``(no span)``, and gaps shorter than ``small`` are summed under
    ``(between ops)``. Returns {name: total}."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0)
    totals = {}

    def add(name, amount):
        totals[name] = totals.get(name, 0) + amount

    for a, b in gaps:
        if b - a < small:
            add("(between ops)", b - a)
            continue
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        near = [s for s in spans[lo:hi] if s[2] > a and s[1] < b]
        cuts = sorted({a, b, *(t for s in near for t in s[1:]
                               if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            over = [s for s in near if s[1] <= mid < s[2]]
            add(min(over, key=lambda s: s[2] - s[1])[0] if over
                else "(no span)", y - x)
    return totals


# --------------------------------------------------------------------------
# reading the profiler's file
# --------------------------------------------------------------------------
def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read(trace_dir, platform):
    """Parse the newest trace under ``trace_dir``. Returns a dict:
    ``devices``: {plane name: [(op name, start_ns, end_ns)]} from each
    device plane's ``XLA Ops`` line; ``spans``: [(name, start_ns,
    end_ns)] of the harness's own annotations; ``lines``: what planes
    and lines the file holds (printed once, for whoever reads a trace
    by hand next). On the CPU (rehearsals only) there is no device
    plane and the XLA thread-pool lines of the host stand in for it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    devices, spans, lines = {}, [], {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and (
            platform.upper() in plane.name.upper())
        lines[plane.name] = []
        for line in plane.lines:
            lines[plane.name].append(line.name)
            if is_dev and line.name == "XLA Ops":
                devices.setdefault(plane.name, []).extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif not plane.name.startswith("/device:"):
                cpu_dev = platform == "cpu" and line.name.startswith(
                    "tf_XLA")
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name[len(PREFIX):], ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif cpu_dev:
                        devices.setdefault("cpu-rehearsal", []).append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    return {"devices": devices, "spans": spans, "lines": lines}


def reduce(parsed, outer, iteration, kernels=None, top=10):
    """From a parsed trace to numbers. The window is from the start of
    the first ``outer`` span in the trace to the end of the last: whole
    iterations only, so the profiler's own start and stop stay outside;
    ``iterations`` counts the ``iteration`` spans inside it.
    ``kernels`` maps a kernel's name to the HLO instruction names its
    custom calls have in the compiled step; an op belongs to a kernel if
    it is one of those or its instruction's name carries the kernel's.
    ``device_ops`` sums ops by family: the kernel, or the instruction's
    name without its number, with a fusion's kind."""
    outers = [s for s in parsed["spans"] if s[0] == outer]
    if not outers or not parsed["devices"]:
        return None
    t0 = min(s[1] for s in outers)
    t1 = max(s[2] for s in outers)
    kernels = kernels or {}
    busy_ns, op_ns, kernel_ns, gap_ns = [], {}, {k: 0 for k in kernels}, {}
    for events in parsed["devices"].values():
        ivals = [(a, b) for _, a, b in events]
        busy_ns.append(busy(ivals, t0, t1))
        for name, a, b in events:
            a, b = max(a, t0), min(b, t1)
            if b <= a:
                continue
            # the chip names an op by its whole HLO instruction:
            # "%fusion.6 = f32[..] fusion(..), kind=kLoop, ..."
            bare = name.split(" = ")[0].lstrip("%")
            family = re.sub(r"[.\d]+$", "", bare)  # fusion.12 -> fusion
            kind = re.search(r"\bkind=(\w+)", name)
            if kind:
                family += f"({kind.group(1)})"
            for k, instrs in kernels.items():
                if bare in instrs or k in bare:
                    kernel_ns[k] += b - a
                    family = k
            op_ns[family] = op_ns.get(family, 0) + (b - a)
        named = name_gaps(idle_gaps(ivals, t0, t1), parsed["spans"],
                          small=SMALL_GAP_NS)
        for n, v in named.items():
            gap_ns[n] = gap_ns.get(n, 0) + v
    chips = len(parsed["devices"])

    def rank(totals):
        return [[n, v / chips / 1e9] for n, v in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / chips / 1e9,
        "chips": chips,
        "iterations": sum(1 for s in parsed["spans"] if s[0] == iteration
                          and s[1] >= t0 and s[2] <= t1),
        "kernel_s": {k: v / chips / 1e9 for k, v in kernel_ns.items()},
        "device_ops": rank(op_ns),
        "idle_gaps": rank(gap_ns),
        "distinct_ops": len(op_ns),
    }
