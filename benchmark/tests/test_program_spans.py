"""The readers of the program's own spans (``benchmark/program_spans.py``):
the slicing and self-time arithmetic on hand-made events, and one traced
``rehearse-serve`` run in which the program's inside counters are held
against the harness's outside copies. From the repository's root:

    python -m pytest benchmark/tests -q
"""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import program_spans, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ev(name, start, end, line="main", **attrs):
    return {"name": name, "start": start, "end": end, "line": line,
            "stats": attrs}


def test_slice_is_from_the_first_to_the_last_harness_span():
    harness = [("router_step", 100, 200), ("engine_step", 110, 190),
               ("router_step", 200, 320), ("router_step", 330, 400)]
    assert program_spans.slice_bounds(harness, "router_step") == (100, 400)
    assert program_spans.slice_bounds(harness, "traced") is None
    events = [ev("engine.step", 90, 150),        # began before the slice
              ev("engine.step", 210, 300),
              ev("engine.step", 100, 200),       # the edges are inside
              ev("engine.step", 390, 401),       # ends after it
              ev("engine.fill", 220, 230)]
    kept = program_spans.in_slice(events, (100, 400))
    assert [(e["name"], e["start"]) for e in kept] == [
        ("engine.step", 100), ("engine.step", 210), ("engine.fill", 220)]


def test_self_time_subtracts_only_what_the_span_contains():
    events = [
        ev("router.step", 0, 100, step=0),
        ev("router.control", 2, 10),
        ev("replica.step", 10, 80, replica="r0"),
        ev("engine.step", 12, 78, step=5),       # a grandchild: not named
        ev("router.collect", 80, 95),
        ev("router.step", 100, 250, step=1),
        ev("replica.step", 110, 150), ev("replica.step", 150, 230),
        # another thread's span over the same interval is not inside
        ev("replica.step", 20, 60, line="producer"),
    ]
    assert program_spans.durations_ms(
        events, "router.step", ("replica.step",)) == [
            (100 - 70) / 1e6, (150 - 120) / 1e6]
    assert program_spans.durations_ms(events, "router.step") == [
        100 / 1e6, 150 / 1e6]
    outer = events[0]
    assert [e["name"] for e in program_spans.contained(
        outer, events, ("router.control", "router.collect"))] == [
            "router.control", "router.collect"]
    assert program_spans.self_ns(outer, events, (
        "router.control", "replica.step", "router.collect")) == 100 - 93
    assert program_spans.stat_values(events, "router.step", "step") == [0, 1]
    assert program_spans.stat_values(events, "replica.step", "replica") == [
        "r0"]
    # nothing to read is None, not zero
    assert stats.percentile(program_spans.durations_ms(
        events, "engine.post"), 50) is None


def test_no_trace_and_no_program_span_read_nothing(tmp_path, monkeypatch):
    from benchmark.readers import span_ms, span_stat_share

    monkeypatch.setattr(program_spans, "TRACE_ROOT", str(tmp_path))
    run = {"trace": {"iterations": 3}, "samples": {},
           "workload": {"engine": {"max_batched_tokens": 512}}}
    assert program_spans.sliced("router_step") == []
    assert span_ms.read(run, "engine.fill", "router_step") is None
    assert span_stat_share.read(run, "engine.dispatch", "router_step",
                                "q_tokens",
                                "engine.max_batched_tokens") is None
    assert span_ms.read(dict(run, trace=None), "engine.fill",
                        "router_step") is None


@pytest.fixture(scope="module")
def traced_serve():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rehearse-serve",
         "--seed", str(2 ** 31 + 9), "--seconds", "4", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


def test_inside_counters_equal_the_spys_outside_copies(traced_serve):
    lines, result = traced_serve
    said, = [ln for ln in lines if ln.startswith("[spans] ")]
    m = re.match(r"\[spans\] dispatches=(\d+) q_tokens=(\[.*?\]) "
                 r"spy_q_tokens=(\[.*?\])$", said)
    inside, outside = json.loads(m.group(2)), json.loads(m.group(3))
    assert int(m.group(1)) == len(inside) > 5
    assert inside == outside
    budget = 64        # rehearse-serve's max_batched_tokens
    assert result["metrics"]["serve.budget_fill"]["value"] == pytest.approx(
        100.0 * stats.percentile(inside, 50) / budget)
    assert 0 < max(inside) <= budget


def test_engine_step_span_is_the_harness_step_seen_from_inside(
        traced_serve):
    """Each ``ptpu:engine.step`` lies inside one harness ``engine_step``
    span and the medians are within 2 %. (``serve.step_wall_ms`` itself
    is the harness's median over the whole window; on the CPU the traced
    slice, its last part, runs at half the speed under the profiler, so
    the span is held to the slice's own harness spans here and to the
    metric on the chip: PERF.md section 6.)"""
    _, result = traced_serve
    parsed = program_spans.parse(
        program_spans.trace.newest_xplane(program_spans.TRACE_ROOT))
    bounds = program_spans.slice_bounds(parsed["harness"], "router_step")
    inside = [e for e in program_spans.in_slice(parsed["program"], bounds)
              if e["name"] == "engine.step"]
    outside = sorted((a, b) for name, a, b in parsed["harness"]
                     if name == "engine_step"
                     and bounds[0] <= a and b <= bounds[1])
    assert len(inside) == len(outside) > 5
    for e, (a, b) in zip(inside, outside):
        assert a <= e["start"] and e["end"] <= b
    mine = stats.percentile([e["end"] - e["start"] for e in inside], 50)
    theirs = stats.percentile([b - a for a, b in outside], 50)
    assert mine == pytest.approx(theirs, rel=0.02)
    # the five children cover the step
    events = program_spans.in_slice(parsed["program"], bounds)
    kids = ("engine.schedule", "engine.fill", "engine.dispatch",
            "engine.fetch", "engine.post")
    left = [program_spans.self_ns(e, events, kids) / (e["end"] - e["start"])
            for e in inside]
    assert stats.percentile(left, 50) < 0.10
    # the router's self time against the harness's pairing of the same
    # two spans, over the same slice
    routers = sorted((a, b) for name, a, b in parsed["harness"]
                     if name == "router_step")
    theirs = stats.percentile(
        [(rb - ra) - (eb - ea)
         for (ra, rb), (ea, eb) in zip(routers, outside)], 50) / 1e6
    mine = result["metrics"]["serve.router_self_ms"]["value"]
    # theirs also holds the replica handle and the harness's own wrapper
    assert len(routers) == len(outside) and 0 < mine < theirs < mine + 0.5
