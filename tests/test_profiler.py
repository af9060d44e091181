"""Profiler: host scopes through dispatch, scheduler windows, chrome
export, summary, throughput timer, MFU (reference profiler.py:346,79,215)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler
from paddle_tpu.profiler import (
    Profiler, ProfilerState, ProfilerTarget, RecordEvent,
    estimate_mfu, export_chrome_tracing, make_scheduler,
)


def test_record_event_scopes_through_dispatch():
    p = Profiler(targets=[ProfilerTarget.CPU]).start()
    x = paddle.randn([8, 8])
    y = paddle.matmul(x, x)
    with RecordEvent("user_scope"):
        _ = paddle.add(y, y)
    p.stop()
    names = {e["name"] for e in p.host_events}
    assert "op::matmul" in names
    assert "op::add" in names
    assert "user_scope" in names
    # hook removed after stop: no growth
    n = len(p.host_events)
    _ = paddle.matmul(x, x)
    assert len(p.host_events) == n


def test_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                           skip_first=1)
    states = [sched(i) for i in range(6)]
    assert states == [ProfilerState.CLOSED, ProfilerState.CLOSED,
                      ProfilerState.READY, ProfilerState.RECORD,
                      ProfilerState.RECORD_AND_RETURN,
                      ProfilerState.CLOSED]


def test_scheduler_windows_and_chrome_export(tmp_path):
    handler = export_chrome_tracing(str(tmp_path))
    p = Profiler(scheduler=make_scheduler(closed=1, ready=0, record=2,
                                          repeat=1),
                 on_trace_ready=handler)
    p.start()
    x = paddle.randn([4, 4])
    for _ in range(4):
        _ = paddle.matmul(x, x)
        p.step()
    p.stop()
    assert p.exported_paths, "trace was never exported"
    with open(p.exported_paths[0]) as f:
        trace = json.load(f)
    assert any(e["name"] == "op::matmul" for e in trace["traceEvents"])


def test_summary_aggregation():
    p = Profiler().start()
    x = paddle.randn([8, 8])
    for _ in range(3):
        _ = paddle.matmul(x, x)
    p.stop()
    stats = p.summary(print_table=False)
    assert stats["op::matmul"]["calls"] == 3
    assert stats["op::matmul"]["total_ms"] > 0


def test_benchmark_timer():
    from paddle_tpu.profiler import benchmark

    b = benchmark()
    b.begin()
    import time

    for _ in range(5):
        time.sleep(0.01)
        b.step(num_samples=32)
    b.end()
    rep = b.report()
    assert rep["steps"] == 5
    assert 5 < rep["avg_step_ms"] < 100
    assert rep["ips"] > 0


def test_estimate_mfu():
    # 1 TFLOP step in 10ms on a 197TFLOP/s chip ~= 50.7%
    mfu = estimate_mfu(1e12, 0.01, peak_flops=197e12)
    assert abs(mfu - 1e12 / 0.01 / 197e12) < 1e-9
    assert 0.4 < mfu < 0.6
    # an unknown device (the CPU here) is an error, not a v5e default;
    # the chip reports "TPU v5 lite", which the table has
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        profiler.device_peak_flops()

    class _V5e:
        device_kind, platform = "TPU v5 lite", "tpu"

    assert profiler.device_peak_flops(_V5e()) == 197e12


def test_device_summary_reports_xla_ops(tmp_path):
    """Per-op device stats from the xplane trace (reference
    profiler_statistic.py device table role)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler

    prof = profiler.Profiler(targets=None, trace_dir=str(tmp_path))
    prof.start()
    f = jax.jit(lambda x: (x @ x).sum())
    jax.block_until_ready(f(jnp.ones((128, 128))))
    prof.stop()
    stats = prof.device_summary(print_table=False)
    assert isinstance(stats, dict)
    if stats:  # device plane present (CPU backend still records XLA ops)
        row = next(iter(stats.values()))
        assert {"calls", "total_ms", "avg_ms"} <= set(row)


@pytest.mark.parametrize("op,phase", [
    ("fusion.123", "compute"),
    ("dot_general.7", "compute"),
    ("all-reduce.1", "collective"),
    ("all-gather-start", "collective"),
    ("reduce-scatter.2", "collective"),
    ("collective-permute.5", "collective"),
    ("copy.4", "copy"),
    ("copy-start.1", "copy"),
    ("copy-done", "copy"),
    ("infeed", "copy"),
    # the chip names an op by its whole instruction: the family is the
    # instruction's own name, not whatever its operands are called (the
    # substring rule behind a July-2026 chip run's copy_frac 0.545 beside
    # MFU 0.698)
    ("%fusion.6 = f32[16]{0} fusion(f32[16]{0} %copy.3), kind=kLoop",
     "compute"),
    ("%copy.3 = bf16[24,2048]{1,0} copy(bf16[24,2048]{0,1} %fusion.9)",
     "copy"),
    ("%copy-done.2 = bf16[8]{0} copy-done((bf16[8]{0}, u32[]) %copy-start.2)",
     "copy"),
    ("%convolution_add_fusion.1 = bf16[4]{0} fusion(%all-reduce.7), "
     "kind=kOutput", "compute"),
    ("%all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4]{0} %copy.1)",
     "collective"),
    ("copy_fusion.2", "compute"),
    ("multiply_reduce_fusion", "compute"),
])
def test_phase_classifier(op, phase):
    """XLA op name -> phase bucket by the op's family (the
    profiler_statistic.py kernel/communication/memcpy categories,
    VERDICT r4 #9)."""
    assert Profiler.classify_phase(op) == phase


def test_phase_summary_graceful_without_device_trace(tmp_path):
    """On backends without a device plane (CPU tests), phase_summary
    returns {} and summary() stays usable."""
    from paddle_tpu import profiler

    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                             trace_dir=str(tmp_path))
    prof.start()
    import paddle_tpu as paddle
    (paddle.ones([8]) * 2).sum()
    prof.stop()
    assert prof.phase_summary(print_table=False) == {}
    s = prof.summary(print_table=False)
    assert "_device_phases" not in s


def test_summary_reports_pipeline_schedule():
    from paddle_tpu import profiler

    class FakeStep:
        schedule = "interleave"
        bubble_fraction = 0.1579
        S, V, M = 4, 2, 8

    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    prof.stop()
    s = prof.summary(print_table=False, pipeline_step=FakeStep())
    assert s["_pipeline_schedule"]["schedule"] == "interleave"
    assert s["_pipeline_schedule"]["bubble_fraction"] == 0.1579
