"""Profiler: host scopes + TPU trace + chrome export + throughput/MFU.

Reference: python/paddle/profiler/profiler.py:346 (scheduler states :79,
export_chrome_tracing :215), host tracer
paddle/fluid/platform/profiler/host_tracer.cc, chrome writer
profiler/chrometracing_logger.cc, timer profiler/timer.py.

TPU mapping: the host side is ``RecordEvent``, the program's one span:
always a ``ptpu:`` TraceAnnotation in the profiler's own trace (one clock
with the device's ops) and, while a Profiler records, kept in memory with
its parent and attributes. It is opened by op dispatch (ops/registry.py
profiler hook), the serving engine and router, the train step, the
prefetcher and user code; the device side delegates to ``jax.profiler``
trace capture (xplane), the TPU's native tracer. ``Profiler.summary()``
aggregates host scopes; ``benchmark()`` is the hapi throughput timer;
``estimate_mfu`` turns step flops + step time into the north-star MFU
number.

Two views of the device's time, both read from the trace's ``XLA Ops``:
REGIONS (``regions.py``: ``DEVICE_REGIONS``, ``region_map``,
``program_regions``, ``Profiler.device_summary(by="region")``) say where
a compiled step's time goes, by the part of the program each operation
belongs to; PHASES (``classify_phase``, ``phase_summary``,
``device_phases``) say what kind of work it was: compute, collective or
copy.
"""
from __future__ import annotations

import itertools
import json
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional

from paddle_tpu.profiler.regions import (  # noqa: F401
    DEVICE_REGIONS, StepProgram, program_regions, region_map, self_times,
)
from paddle_tpu.profiler.timer import Benchmark, benchmark  # noqa: F401

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "span", "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "benchmark", "estimate_mfu",
           "device_phases", "register_counter_provider",
           "unregister_counter_provider", "counters", "DEVICE_REGIONS",
           "region_map", "program_regions"]


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget:
    CPU = 0
    GPU = 1      # accepted for API parity; no-op
    CUSTOM_DEVICE = 2
    TPU = 3


# ---------------------------------------------------------------------------
# host event recorder
# ---------------------------------------------------------------------------
class _HostEventRecorder:
    def __init__(self):
        self.events: List[dict] = []
        self.active = False
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        # per-thread stack of the ids of the spans open on that thread
        # (the prefetcher's producer has its own): a span's parent is
        # the top of its own thread's stack
        self._open = threading.local()

    def start(self):
        self.events = []
        self.active = True

    def stop(self):
        self.active = False

    def push(self):
        """Open a span on this thread: returns ``(id, parent id)``."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        sid = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent

    def pop(self, sid):
        stack = getattr(self._open, "stack", ())
        if sid in stack:    # begin()/end() pairs need not nest
            stack.remove(sid)

    def add(self, name, ts_us, dur_us, sid, parent, args):
        if not self.active:
            return
        event = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
                 "pid": os.getpid(), "tid": threading.get_ident() % 100000,
                 "id": sid, "parent": parent, "args": args}
        with self._lock:
            self.events.append(event)


_recorder = _HostEventRecorder()
_TraceAnnotation = None     # jax.profiler.TraceAnnotation, bound on first use


def _bind_annotation():
    global _TraceAnnotation
    from jax.profiler import TraceAnnotation

    _TraceAnnotation = TraceAnnotation
    return TraceAnnotation


class RecordEvent:
    """The program's one span (reference profiler/event_tracing.h
    RecordEvent). ``RecordEvent(name, **attrs)``, as a context manager or
    a decorator; ``profiler.span`` is the same class.

    Entering always opens ``jax.profiler.TraceAnnotation("ptpu:" + name,
    **attrs)``: with no profiler session that is a sub-microsecond no-op
    (the attributes are not formatted); with one (``jax.profiler`` or a
    :class:`Profiler` with the TPU target) the span lands in the same
    ``.xplane.pb`` as the device's ``XLA Ops``, on the same clock, and
    the attributes come back as the event's typed stats. While a
    :class:`Profiler` records, the span is also kept in memory with its
    ``id``, its ``parent`` (the span open around it on the same thread)
    and its ``args``, for the chrome export and ``summary()``.

    Attributes are plain ints, floats and short strings the caller
    already holds; nothing is fetched from a device to fill one."""

    __slots__ = ("name", "attrs", "_ann", "_t0", "_id", "_parent")

    def __init__(self, name: str, event_type=None, **attrs):
        self.name = name
        self.attrs = attrs
        self._ann = self._t0 = None

    def __enter__(self):
        ann = self._ann = (_TraceAnnotation or _bind_annotation())(
            "ptpu:" + self.name, **self.attrs)
        ann.__enter__()
        if _recorder.active:
            self._id, self._parent = _recorder.push()
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            t1 = time.perf_counter_ns()
            _recorder.pop(self._id)
            _recorder.add(self.name, self._t0 / 1e3, (t1 - self._t0) / 1e3,
                          self._id, self._parent, self.attrs)
            self._t0 = None
        self._ann.__exit__(None, None, None)
        return False

    def set(self, **attrs):
        """Attributes known only once the span is open (what a step
        emitted): joined to the open span's."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    begin = __enter__

    def end(self):
        if self._ann is not None:
            self.__exit__()
            self._ann = None

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with RecordEvent(self.name, **self.attrs):
                return fn(*a, **k)

        return wrapped


span = RecordEvent


# ---------------------------------------------------------------------------
# scheduler (reference profiler.py:79 — cycle through window states)
# ---------------------------------------------------------------------------
def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], int]:
    """Returns fn(step)->state cycling CLOSED*closed, READY*ready,
    RECORD*(record-1), RECORD_AND_RETURN, repeated ``repeat`` times
    (0 = forever), after ``skip_first`` skipped steps."""
    assert record > 0, "record window must be positive"
    span = closed + ready + record

    def fn(step: int) -> int:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * span:
            return ProfilerState.CLOSED
        pos = s % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos < span - 1:
            return ProfilerState.RECORD
        return ProfilerState.RECORD_AND_RETURN

    return fn


def _default_scheduler(step: int) -> int:
    return ProfilerState.RECORD  # record everything between start/stop


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler writing chrome://tracing JSON
    (reference profiler.py:215)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_step{prof.step_num}.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": prof.host_events}, f)
        prof.exported_paths.append(path)

    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Profiler
# ---------------------------------------------------------------------------
class Profiler:
    """Reference profiler.py:346 contract: targets, scheduler windows,
    on_trace_ready, start/step/stop, summary."""

    def __init__(self, *, targets=None, scheduler=None,
                 on_trace_ready=None, timer_only: bool = False,
                 record_op_events: bool = True, trace_dir: Optional[str] = None):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if scheduler is None:
            self._sched = _default_scheduler
        elif callable(scheduler):
            self._sched = scheduler
        else:  # (start, end) tuple like the reference accepts
            lo, hi = scheduler
            self._sched = make_scheduler(
                closed=max(lo, 0), ready=0, record=hi - lo, repeat=1)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.record_op_events = record_op_events
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self.host_events: List[dict] = []
        self.exported_paths: List[str] = []
        self._device_tracing = False
        self._trace_dir = trace_dir or "/tmp/paddle_tpu_trace"
        # set when THIS profiler started a device trace; xplane files
        # older than it (stale runs sharing the default dir) are ignored
        self._trace_token: Optional[float] = None

    # -- state transitions ------------------------------------------------
    def _recording(self, state):
        return state in (ProfilerState.RECORD,
                         ProfilerState.RECORD_AND_RETURN)

    def _enter_record(self):
        if self.timer_only:
            return
        _recorder.start()
        if self.record_op_events:
            from paddle_tpu.ops import registry as _registry

            _registry.set_profiler_hook(lambda name: RecordEvent(name))
        if ProfilerTarget.TPU in self.targets:
            try:
                import jax

                # the Python tracer floods the trace and slows the host
                # it measures; host annotations (the ``ptpu:`` spans)
                # stay on
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                self._trace_token = time.time()
                jax.profiler.start_trace(self._trace_dir,
                                         profiler_options=opts)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False
                self._trace_token = None

    def _exit_record(self):
        if self.timer_only:
            return
        _recorder.stop()
        self.host_events = list(_recorder.events)
        from paddle_tpu.ops import registry as _registry

        _registry.set_profiler_hook(None)
        if self._device_tracing:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def start(self):
        self.state = self._sched(self.step_num)
        if self._recording(self.state):
            self._enter_record()
        benchmark().begin()
        return self

    def step(self, num_samples: Optional[int] = None):
        benchmark().step(num_samples)
        self.step_num += 1
        new = self._sched(self.step_num)
        if self._recording(new) and not self._recording(self.state):
            self._enter_record()
        elif self._recording(self.state) and not self._recording(new):
            self._exit_record()
        self.state = new

    def stop(self):
        if self._recording(self.state):
            self._exit_record()
        self.state = ProfilerState.CLOSED
        benchmark().end()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting --------------------------------------------------------
    def export(self, path: str):
        with open(path, "w") as f:
            json.dump({"traceEvents": self.host_events}, f)
        return path

    def summary(self, sorted_by="total", print_table: bool = True,
                pipeline_step=None):
        """Aggregate host events by name -> calls/total/self/avg/max ms
        (self = a span's duration minus the part its child spans cover);
        when a device trace was captured, append the per-phase breakdown
        (phase_summary); when a PipelineTrainStep is passed, report its
        schedule + bubble fraction (reference profiler_statistic.py
        step-category report, VERDICT r4 #9)."""
        covered: Dict[int, float] = {}      # span id -> its children's us
        for e in self.host_events:
            if e.get("parent") is not None:
                covered[e["parent"]] = covered.get(e["parent"], 0.0) \
                    + e["dur"]
        agg: Dict[str, List[float]] = {}
        self_ms: Dict[str, float] = {}
        for e in self.host_events:
            agg.setdefault(e["name"], []).append(e["dur"] / 1e3)  # ms
            self_ms[e["name"]] = self_ms.get(e["name"], 0.0) + (
                e["dur"] - covered.get(e.get("id"), 0.0)) / 1e3
        rows = [(k, len(v), sum(v), sum(v) / len(v), max(v), self_ms[k])
                for k, v in agg.items()]
        rows.sort(key=lambda r: -r[2])
        if print_table:
            hdr = (f"{'Event':<44}{'Calls':>8}{'Total(ms)':>12}"
                   f"{'Self(ms)':>12}{'Avg(ms)':>10}{'Max(ms)':>10}")
            print(hdr)
            print("-" * len(hdr))
            for nm, c, tot, avg, mx, own in rows[:40]:
                print(f"{nm:<44}{c:>8}{tot:>12.3f}{own:>12.3f}"
                      f"{avg:>10.3f}{mx:>10.3f}")
        out = {r[0]: {"calls": r[1], "total_ms": r[2], "avg_ms": r[3],
                      "max_ms": r[4], "self_ms": r[5]} for r in rows}
        try:
            phases = self.phase_summary(print_table=print_table)
        except Exception:
            phases = {}
        if phases:
            out["_device_phases"] = phases
        if pipeline_step is not None:
            sched = {
                "schedule": pipeline_step.schedule,
                "bubble_fraction": round(
                    pipeline_step.bubble_fraction, 4),
                "stages": pipeline_step.S,
                "interleave_degree": pipeline_step.V,
                "n_microbatches": pipeline_step.M,
            }
            out["_pipeline_schedule"] = sched
            if print_table:
                print(f"pipeline: {sched['schedule']} S={sched['stages']}"
                      f" V={sched['interleave_degree']}"
                      f" M={sched['n_microbatches']}"
                      f" bubble={sched['bubble_fraction']}")
        return out

    def _load_trace(self):
        """The xplane trace THIS profiler captured, or None. Files that
        predate this profiler's start_trace (stale runs sharing the
        default trace dir) are ignored — without the token filter a
        CPU-only run would report a previous run's device phases as its
        own."""
        if self._trace_token is None:
            return None
        return _latest_trace(self._trace_dir,
                             min_mtime=self._trace_token - 1.0)

    def device_summary(self, top: int = 40, print_table: bool = True,
                       by: str = "op"):
        """DEVICE time table from the captured xplane trace — the device
        half of the reference's profiler_statistic.py report (kernel
        stats aggregated from CUPTI there, from the TPU/XLA xplane here).
        Requires the profiler to have run with device tracing (the
        default when jax.profiler capture is available).

        ``by="op"``: one row an op as the trace names it (calls, total
        and average ms). ``by="region"``: where the compiled steps' time
        goes — one row a member of ``DEVICE_REGIONS`` (plus ``unscoped``
        and, for a train step, ``<region>.backward``): calls, total ms,
        SELF ms (a ``while`` no longer counts its body twice) and the
        share of the device's busy time, read through
        :func:`program_regions` (which lowers the steps dispatched under
        this session once, after it). ``phase_summary`` is the other
        view: compute / collective / copy."""
        pd = self._load_trace()
        if pd is None:
            return {}
        if by == "region":
            return _regions_from_trace(pd, top, print_table)
        agg: Dict[str, List[float]] = {}
        for name, dur_ms in _iter_device_ops(pd):
            agg.setdefault(name, []).append(dur_ms)
        rows = [(k, len(v), sum(v), sum(v) / len(v))
                for k, v in agg.items()]
        rows.sort(key=lambda r: -r[2])
        if print_table and rows:
            hdr = (f"{'Device op':<52}{'Calls':>8}{'Total(ms)':>12}"
                   f"{'Avg(ms)':>10}")
            print(hdr)
            print("-" * len(hdr))
            for nm, c, tot, avg in rows[:top]:
                print(f"{nm[:52]:<52}{c:>8}{tot:>12.3f}{avg:>10.3f}")
        return {r[0]: {"calls": r[1], "total_ms": r[2], "avg_ms": r[3]}
                for r in rows}

    _PHASE_COLLECTIVE = ("all-reduce", "all-gather", "all-to-all",
                         "reduce-scatter", "collective-permute",
                         "collective-broadcast", "psum", "ppermute")
    _PHASE_COPY = ("copy", "infeed", "outfeed", "transfer", "memcpy",
                   "h2d", "d2h")

    @classmethod
    def classify_phase(cls, op_name: str) -> str:
        """XLA op name -> phase bucket (compute | collective | copy), by
        the op's FAMILY: the instruction's own name without its number
        (``copy.4``, ``copy-start.1``, ``all-gather-done``). The chip
        names an op by its whole instruction (``%fusion.6 = f32[..]
        fusion(.. %copy.3), kind=kLoop``), so a substring rule files
        every fusion that reads a copy under copies (the July-2026 chip
        run's ``copy_frac`` 0.545)."""
        family = re.sub(r"[.\d]+$", "", _instruction(op_name)).lower()

        def among(tokens):
            return any(family == t or family.startswith(t + "-")
                       for t in tokens)

        if among(cls._PHASE_COLLECTIVE):
            return "collective"
        if among(cls._PHASE_COPY):
            return "copy"
        return "compute"

    def phase_summary(self, print_table: bool = True):
        """Per-phase DEVICE time breakdown from the xplane trace —
        compute vs collective vs data movement (the reference's
        profiler_statistic.py step breakdown: kernel / communication /
        memcpy categories). Fractions are of total device-busy time, so
        'collective_frac' reads directly as the comm share of a step
        (VERDICT r4 #9)."""
        pd = self._load_trace()
        if pd is None:
            return {}
        return _phases_from_trace(pd, print_table=print_table)


# ---------------------------------------------------------------------------
# trace loading + device-op iteration (shared by Profiler and the public
# device_phases API)
# ---------------------------------------------------------------------------
def _instruction(op_name: str) -> str:
    """The HLO instruction's own name of a trace event: the chip names
    an op by its whole instruction (``%fusion.6 = f32[..] fusion(..)``)."""
    return op_name.split(" = ")[0].strip().lstrip("%")


def _read_xspace(path: str):
    """One parsed trace file, through jax's own reader."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _latest_trace(trace_dir: str, min_mtime: Optional[float] = None):
    import glob

    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if min_mtime is not None:
        files = [f for f in files if os.path.getmtime(f) >= min_mtime]
    for f in reversed(files):
        try:
            return _read_xspace(f)
        except Exception:
            # an external run may still be flushing its newest file —
            # a truncated trace is skipped, not fatal
            continue
    return None


# XLA:CPU runs ops on host threadpool lines; these events on those lines
# are executor bookkeeping, not ops
_CPU_INFRA_EVENTS = ("ThreadpoolListener", "ThunkExecutor",
                     "TaskDispatcher")


def _device_planes(pd):
    return [p for p in pd.planes
            if "TPU" in p.name or "GPU" in p.name
            or "device" in p.name.lower()]


def _device_op_lines(pd):
    """Yield, for every trace line that holds XLA op executions, its
    events as ``[(op_name, start_ns, end_ns)]``. TPU/GPU traces put ops
    on a device plane's 'XLA Ops' line; XLA:CPU has no device plane — its
    ops run on '/host:CPU' threadpool lines named 'tf_XLA*' (used only
    when no device plane exists, so a TPU trace never double-counts
    host-side helpers)."""
    device_planes = _device_planes(pd)
    if any(line.name == "XLA Ops" for p in device_planes
           for line in p.lines):
        for plane in device_planes:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    yield [(ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events]
        return
    for plane in pd.planes:
        if "host:CPU" not in plane.name:
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                yield [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events
                       if not any(t in ev.name for t in _CPU_INFRA_EVENTS)]


def _iter_device_ops(pd):
    """Yield (op_name, duration_ms) for every XLA op execution in a
    parsed trace."""
    for events in _device_op_lines(pd):
        for name, a, b in events:
            yield name, (b - a) / 1e6


def _regions_from_trace(pd, top: int, print_table: bool) -> dict:
    """Device SELF time by region (``device_summary(by="region")``). The
    trace names an op by its HLO instruction (``%fusion.6 = ...`` on the
    chip); the steps' maps say which region each belongs to. Steps of
    several programs in one trace share instruction names: the first
    program that knows a name files it."""
    maps = [m for m in program_regions().values() if m]
    if not maps:
        return {}
    rows: Dict[str, List[float]] = {}       # region -> [calls, total, self]
    for events in _device_op_lines(pd):
        for name, duration, own in self_times(events):
            bare = _instruction(name)
            at = next((m[bare] for m in maps if bare in m), None)
            region = (at and at["region"]) or "unscoped"
            if at and at["backward"]:
                region += ".backward"
            row = rows.setdefault(region, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += duration / 1e6
            row[2] += own / 1e6
    busy = sum(r[2] for r in rows.values())
    ranked = sorted(rows.items(), key=lambda kv: -kv[1][2])
    if print_table and ranked:
        hdr = (f"{'Region':<36}{'Calls':>8}{'Total(ms)':>12}"
               f"{'Self(ms)':>12}{'Share':>8}")
        print(hdr)
        print("-" * len(hdr))
        for nm, (c, tot, own) in ranked[:top]:
            print(f"{nm:<36}{c:>8}{tot:>12.3f}{own:>12.3f}"
                  f"{own / busy if busy else 0.0:>8.3f}")
    return {nm: {"calls": c, "total_ms": tot, "self_ms": own,
                 "share": own / busy if busy else 0.0}
            for nm, (c, tot, own) in ranked}


def _phases_from_trace(pd, print_table: bool = False) -> dict:
    phases = {"compute": 0.0, "collective": 0.0, "copy": 0.0}
    counts = {"compute": 0, "collective": 0, "copy": 0}
    for name, dur_ms in _iter_device_ops(pd):
        ph = Profiler.classify_phase(name)
        phases[ph] += dur_ms
        counts[ph] += 1
    steps = 0
    for plane in _device_planes(pd):
        for line in plane.lines:
            if line.name == "Steps":
                steps = max(steps, sum(1 for _ in line.events))
    total = sum(phases.values())
    out = {f"{k}_ms": round(v, 3) for k, v in phases.items()}
    out["total_device_ms"] = round(total, 3)
    out["steps_captured"] = steps
    for k, c in counts.items():
        out[f"{k}_ops"] = c
    if total > 0:
        for k, v in phases.items():
            out[f"{k}_frac"] = round(v / total, 4)
    if print_table and total > 0:
        print(f"{'Phase':<14}{'Total(ms)':>12}{'Ops':>8}{'Fraction':>10}")
        print("-" * 44)
        for k, v in phases.items():
            print(f"{k:<14}{v:>12.3f}{counts[k]:>8}{v / total:>10.3f}")
    return out


def _sync_tree(x):
    """Force the device queue to drain before the trace window closes:
    every array leaf is blocked on, then one scalar is HOST-FETCHED from
    the last leaf, so the window holds the trailing ops (the copies this
    API exists to measure among them)."""
    leaves = []

    def walk(v):
        if v is None:
            return
        if isinstance(v, (list, tuple)):
            for u in v:
                walk(u)
            return
        if isinstance(v, dict):
            for u in v.values():
                walk(u)
            return
        d = getattr(v, "_data", v)  # Tensor -> jax.Array
        if hasattr(d, "block_until_ready"):
            leaves.append(d)

    walk(x)
    import numpy as _np

    for d in leaves:
        try:
            d.block_until_ready()  # tpulint: disable=block-until-ready-in-loop (trace-window close barrier: every leaf must retire before the profile stops; runs once per trace, not per step)
        except Exception:
            pass
    if leaves:
        d = leaves[-1]
        try:
            # fetch the whole array when tiny (the usual scalar loss),
            # else one element — either way a real host round-trip
            _np.asarray(d if d.size <= 1024 else d.ravel()[:1])
        except Exception:
            pass


def device_phases(step_fn: Optional[Callable] = None, *, steps: int = 3,
                  warmup: int = 1, trace_dir: Optional[str] = None,
                  print_table: bool = False) -> dict:
    """Device-phase breakdown — compute vs collective vs copy — as a
    first-class metric (keys: ``{phase}_ms``, ``{phase}_ops``,
    ``{phase}_frac``, ``total_device_ms``, ``steps_captured``).

    Two modes:

    * ``device_phases(fn, steps=3)`` — call ``fn()`` ``warmup`` times
      un-traced (compile outside the measured window), then ``steps``
      times under a fresh device trace, sync the last result, and return
      the breakdown. The ``copy_frac`` it returns is the number the input-pipeline work
      (donated train-step buffers, ``io.DevicePrefetcher``) is driving
      down.
    * ``device_phases(trace_dir=...)`` — parse the newest xplane trace
      already captured under ``trace_dir`` (e.g. by an external run).

    Returns ``{}`` when no device trace can be obtained (device tracing
    unavailable on the backend)."""
    if step_fn is None:
        if trace_dir is None:
            raise ValueError(
                "device_phases needs a step_fn to profile or a trace_dir "
                "holding an existing xplane trace")
        pd = _latest_trace(trace_dir)
        if pd is None:
            return {}
        return _phases_from_trace(pd, print_table=print_table)
    import tempfile

    out = None
    for _ in range(max(0, warmup)):
        out = step_fn()
    _sync_tree(out)
    own_dir = None
    if trace_dir is None:
        trace_dir = own_dir = tempfile.mkdtemp(prefix="ptpu_phases_")
    prof = Profiler(
        targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
        trace_dir=trace_dir)
    try:
        prof.start()
        try:
            for _ in range(max(1, steps)):
                out = step_fn()
            _sync_tree(out)
        finally:
            prof.stop()
        return prof.phase_summary(print_table=print_table)
    finally:
        if own_dir is not None:
            import shutil

            shutil.rmtree(own_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# MFU (the north star's gate: >=45% at 8B)
# ---------------------------------------------------------------------------
_PEAK_BF16_FLOPS = {
    # per-chip peak dense bf16 FLOP/s (public spec sheets), matched as a
    # substring of the lower-cased ``device_kind``
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def device_peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s of ``device`` (default: the first one). A
    device kind the table does not hold is an error, never a default —
    a utilization against a guessed peak is not a measurement."""
    import jax

    d = device or jax.devices()[0]
    kind = d.device_kind.lower()
    for k, v in _PEAK_BF16_FLOPS.items():
        if k in kind:
            return v
    raise ValueError(
        f"no peak FLOP/s known for device kind {d.device_kind!r} "
        f"(platform {d.platform!r}); known: {sorted(_PEAK_BF16_FLOPS)}")


def estimate_mfu(flops_per_step: float, step_time_s: float,
                 peak_flops: Optional[float] = None) -> float:
    """Model FLOPs utilisation: achieved / peak."""
    peak = peak_flops or device_peak_flops()
    return flops_per_step / max(step_time_s, 1e-12) / peak


# ---------------------------------------------------------------------------
# observability counters (pull model: reading a counter may sync device
# state, so providers are only invoked when counters() is called — never
# per step)
# ---------------------------------------------------------------------------
_counter_providers: Dict[str, Callable] = {}
# registrations arrive from arbitrary threads (weakref.finalize callbacks
# fire on whichever thread drops the last reference); the lock covers the
# dict, not the providers — counters() calls those outside it because a
# provider may itself sync device state or take the caller's locks
_prov_lock = threading.Lock()


def register_counter_provider(name: str, fn: Callable) -> None:
    """Register a zero-arg callable whose value appears in
    :func:`counters` under ``name``. Used by e.g. TrainStep's
    ``skip_nonfinite`` guard to surface its device-carried skip count.
    A provider returning None (dead weakref) is dropped."""
    with _prov_lock:
        _counter_providers[name] = fn


def unregister_counter_provider(name: str) -> None:
    with _prov_lock:
        _counter_providers.pop(name, None)


def counters() -> Dict[str, float]:
    """Current values of every registered observability counter."""
    with _prov_lock:
        providers = list(_counter_providers.items())
    out = {}
    dead = []
    for name, fn in providers:
        try:
            v = fn()
        except Exception:
            continue
        if v is None:  # provider's subject was garbage-collected
            dead.append(name)
            continue
        out[name] = v
    if dead:
        with _prov_lock:
            for name in dead:
                _counter_providers.pop(name, None)
    return out
