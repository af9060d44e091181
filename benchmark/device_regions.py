"""Device SELF time by region of the program, and by kind of step.

The program names the parts of its compiled steps (``jax.named_scope``
regions) and publishes, after the run, which region every HLO instruction
of a step belongs to (``paddle_tpu.profiler.program_regions()``). This
module reads the run's profiler trace the way ``program_spans`` does (the
newest ``.xplane.pb`` under ``.bench_out/trace``), nests each device
line's ``XLA Ops`` events by containment (a ``while`` encloses its body's
ops; an event's self time is its duration minus what it encloses, so self
times sum to the busy union and nothing is counted twice), and files each
event's self time under its instruction's region.

A program without ``program_regions`` (the parent of the PR that added
it), or one whose compiled text shows none of the regions every step has
(a stale executable of a shared compile cache), yields nothing, and every
reader over this module then reads None. The arithmetic is pure and
checked on hand-made events in ``tests/test_device_regions.py``.

Step kinds assume the host and the device run in SERIES (the engine
fetches a step's tokens before it schedules the next): the device time
between one ``ptpu:engine.dispatch`` start and the next belongs to the
first. Once steps overlap, this needs the ``XLA Modules`` line instead.
"""
from __future__ import annotations

import bisect
import functools
import time

from benchmark import program_spans, stats, trace

UNSCOPED = "unscoped"
DISPATCH = "engine.dispatch"


# --------------------------------------------------------------------------
# arithmetic on events: (name, start, end) in ns, one trace line at a time
# --------------------------------------------------------------------------
def bare(name):
    """The HLO instruction's name as ``trace.reduce`` cuts it: the chip
    names an op by its whole instruction (``%fusion.6 = f32[..] ...``)."""
    return name.split(" = ")[0].strip().lstrip("%")


def clipped(events, t0, t1):
    """The events cut to [t0, t1] (an op the slice's edge cuts keeps the
    part inside)."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if min(b, t1) > max(a, t0)]


def self_times(events):
    """``[(name, start, end)]`` of ONE line -> ``[(name, start, self)]``.
    Events nest by containment: one that starts inside an open event is
    its child; an event's self time is its duration minus its children's.
    Touching events are siblings."""
    out, stack = [], []             # stack of [name, start, end, self]

    def close(until):
        while stack and stack[-1][2] <= until:
            name, start, _, own = stack.pop()
            out.append((name, start, own))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            b = min(b, stack[-1][2])        # a child ends with its parent
            stack[-1][3] -= b - a
        stack.append([name, a, b, b - a])
    close(float("inf"))
    return out


def file_by_region(selfs, placed):
    """Self times summed by where ``placed`` (the program's
    ``{instruction: {"region", "backward", "mixed"}}``) puts each event:
    ``{(region or UNSCOPED, backward): ns}``, the ns in fusions whose
    members come from several regions, and the ns per instruction that
    has no region (for the fact line)."""
    by, mixed, outside = {}, 0, {}
    for name, _, own in selfs:
        at = placed.get(bare(name))
        region = (at and at["region"]) or UNSCOPED
        key = (region, bool(at and at["backward"]))
        by[key] = by.get(key, 0) + own
        if at and at["mixed"]:
            mixed += own
        if region == UNSCOPED:
            family = bare(name).rstrip("0123456789.")
            outside[family] = outside.get(family, 0) + own
    return by, mixed, outside


def step_kinds(dispatches, selfs, t1):
    """Device busy ns of each step: the self times that start between one
    dispatch's start and the next's (the last step ends at ``t1``).
    ``dispatches``: [(start, stats)] in start order. Returns
    [(stats, busy ns)]."""
    starts = [d[0] for d in dispatches]
    busy = [0] * len(dispatches)
    for _, at, own in selfs:
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and at < t1:
            busy[i] += own
    return [(d[1], b) for d, b in zip(dispatches, busy)]


# --------------------------------------------------------------------------
# reading the run's trace and the program's map
# --------------------------------------------------------------------------
def program_maps(family):
    """The program's ``{instruction: place}`` for the steps called
    ``family`` (``"serve.step"``: also ``serve.step.tiered``, ...), and
    the seconds it took to get; ({}, 0.0) from a program that publishes
    none."""
    try:
        from paddle_tpu import profiler
    except ImportError:
        return {}, 0.0
    publish = getattr(profiler, "program_regions", None)
    if publish is None:
        return {}, 0.0
    t0 = time.perf_counter()
    placed = {}
    for name, regions in publish().items():
        if name == family or name.startswith(family + "."):
            placed.update(regions)
    return placed, time.perf_counter() - t0


@functools.lru_cache(maxsize=4)
def load(platform, within, family, iterations):
    """Everything the readers need of the newest trace's ``within``
    slice, or None: ``busy_ns`` (the self times' sum, a chip), ``by``
    {(region, backward): self ns a chip}, ``mixed_ns``, ``steps``
    [(dispatch stats, busy ns)]. Prints the ``[regions]`` fact lines,
    once (the result is cached)."""
    placed, took = program_maps(family)
    if not placed:
        return None
    try:
        path = trace.newest_xplane(program_spans.TRACE_ROOT)
    except FileNotFoundError:
        return None
    parsed = program_spans.parse(path)
    bounds = program_spans.slice_bounds(parsed["harness"], within)
    lines = [clipped(events, *bounds) for events in
             device_lines(path, platform)] if bounds else []
    if not any(lines):
        return None
    by, mixed, outside, selfs_all, busy = {}, 0, {}, [], 0
    for events in lines:
        selfs = self_times(events)
        selfs_all.extend(selfs)
        part, mix, out = file_by_region(selfs, placed)
        for k, v in part.items():
            by[k] = by.get(k, 0) + v
        for k, v in out.items():
            outside[k] = outside.get(k, 0) + v
        mixed += mix
        busy += trace.busy([(a, b) for _, a, b in events], *bounds)
    total = sum(by.values())
    # a line's events nest, so its self times sum to its busy union
    if abs(total - busy) > 0.005 * busy:
        print(f"[regions] self times sum to {total / 1e9:.4f} s, the busy "
              f"union is {busy / 1e9:.4f} s: the device's events do not "
              f"nest; no region metric is reported", flush=True)
        return None
    # a chip has one line; a CPU rehearsal's thread-pool lines stand in
    # for one device
    chips = len(lines) if platform == "tpu" else 1
    # in_slice hands the events back in start order
    dispatches = [(e["start"], e["stats"]) for e in program_spans.in_slice(
        parsed["program"], bounds) if e["name"] == DISPATCH]
    loaded = {"busy_ns": total / chips, "mixed_ns": mixed / chips,
              "by": {k: v / chips for k, v in by.items()},
              "outside": {k: v / chips for k, v in outside.items()},
              "steps": step_kinds(dispatches, selfs_all, bounds[1]),
              "self_over_busy": total / busy if busy else 0.0,
              "regions_s": took}
    say(loaded, iterations)
    return loaded


def device_lines(path, platform):
    """The device's op events LINE BY LINE, one list a line: a TPU
    plane's ``XLA Ops``; on the CPU (rehearsals only) the host's XLA
    thread-pool lines, as ``trace.read`` takes them."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:") and (
            platform.upper() in plane.name.upper())
        on_host = platform == "cpu" and not plane.name.startswith("/device:")
        for line in plane.lines:
            if (on_device and line.name == "XLA Ops") or (
                    on_host and line.name.startswith("tf_XLA")):
                yield [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events
                       if not ev.name.startswith((trace.PREFIX,
                                                  program_spans.PREFIX))]


def say(loaded, iterations):
    """The cell's fact line: ms a step by region in descending order,
    with ``unscoped`` and ``mixed``, so the next PERF.md section 5 is
    copied, not reconstructed."""
    per = 1e6 * max(iterations, 1)
    rows = sorted(loaded["by"].items(), key=lambda kv: -kv[1])
    body = " ".join(
        f"{region}{'.backward' if backward else ''}={ns / per:.3f}"
        for (region, backward), ns in rows)
    print(f"[regions] ms/step busy={loaded['busy_ns'] / per:.3f} {body} "
          f"mixed={loaded['mixed_ns'] / per:.3f} "
          f"self_over_busy={loaded['self_over_busy']:.4f} "
          f"program_regions_s={loaded['regions_s']:.2f}", flush=True)
    left = sorted(loaded["outside"].items(), key=lambda kv: -kv[1])[:8]
    if left:
        print("[regions] unscoped by op family, ms/step: " + " ".join(
            f"{family}={ns / per:.3f}" for family, ns in left), flush=True)
    kinds = by_kind(loaded["steps"])
    if kinds:
        print("[regions] device ms by step kind: " + " ".join(
            f"{kind}: n={len(v)} median={stats.percentile(v, 50):.3f}"
            for kind, v in sorted(kinds.items())), flush=True)


def by_kind(steps):
    """{"decode" | "chunk": [device ms]} of the steps whose dispatch span
    says what they held: ``decode`` holds no prefill row."""
    kinds = {}
    for attrs, busy in steps:
        if "prefill_rows" not in attrs:
            continue
        kind = "chunk" if attrs["prefill_rows"] else "decode"
        kinds.setdefault(kind, []).append(busy / 1e6)
    return kinds


def for_run(run, within, family):
    """``load`` for a reader's ``run``: nothing without a traced slice."""
    if not run["trace"] or not run["trace"]["iterations"]:
        return None
    return load(run["workload"].get("platform", "tpu"), within, family,
                run["trace"]["iterations"])
