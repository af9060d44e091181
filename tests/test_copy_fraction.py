"""Perf smoke test (slow-marked): donation actually removes copies.

The regression this tripwires: someone drops ``donate_argnums`` (or
breaks the aliasing contract) and every step silently goes back to
allocate-and-copy for the whole parameter/optimizer state — exactly the
copy_frac=0.545 regime the July-2026 chip run measured. Runs entirely on CPU: XLA:CPU
honors input/output aliasing, a frozen (stop_gradient) parameter is a
pass-through output that MUST be copied without donation and aliased
with it, so the donated executable provably contains and executes fewer
copy ops. Verified two ways — statically in the compiled HLO, and
dynamically by counting copy events with profiler.device_phases over a
tiny compiled step loop.
"""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler

pytestmark = pytest.mark.slow


def _fresh(donate):
    paddle.seed(5)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    # frozen first layer: its weight/bias thread through the step
    # unchanged — pass-through outputs are where undonated executables
    # must materialize copies
    for p in m[0].parameters():
        p.stop_gradient = True
    opt = optimizer.Adam(learning_rate=0.01, parameters=m.parameters())
    return m, paddle.jit.TrainStep(m, nn.CrossEntropyLoss(), opt,
                                   donate=donate)


def _batch():
    rng = np.random.default_rng(0)
    X = paddle.to_tensor(rng.normal(size=(16, 8)).astype("float32"))
    Y = paddle.to_tensor(rng.integers(0, 4, 16).astype("int64"))
    return X, Y


def _compiled_text(step, X, Y):
    """The HLO text of the step exactly as TrainStep dispatches it."""
    args = (1, step._carry, [p._data for p in step._params],
            step._slots, [b._data for b in step._buffers],
            step._lr_arr, step._scaler_state, X._data, Y._data)
    return step._jitted.lower(*args).compile().as_text()


def _count_hlo_copies(text):
    return len(re.findall(r"= \S+ copy\(", text))


def test_donated_step_issues_fewer_copy_ops():
    X, Y = _batch()
    _, step_d = _fresh(donate=True)
    _, step_u = _fresh(donate=False)
    step_d(X, Y)  # compile + set _lr_arr
    step_u(X, Y)

    # static check: the donated executable aliases state into place
    txt_d = _compiled_text(step_d, X, Y)
    txt_u = _compiled_text(step_u, X, Y)
    assert "input_output_alias" in txt_d
    assert "input_output_alias" not in txt_u
    copies_d, copies_u = _count_hlo_copies(txt_d), _count_hlo_copies(txt_u)
    assert copies_d < copies_u, (
        f"donated step compiled to {copies_d} copy ops vs {copies_u} "
        f"undonated — donation is not removing copies")

    # dynamic check: run a tiny step loop under the profiler and count
    # executed copy ops via the public phase API (skipped, not failed,
    # if this platform produces no usable trace)
    ph_d = profiler.device_phases(lambda: step_d(X, Y), steps=3, warmup=0)
    ph_u = profiler.device_phases(lambda: step_u(X, Y), steps=3, warmup=0)
    if not ph_d or not ph_u or ph_u.get("total_device_ms", 0) == 0:
        pytest.skip("no device trace available on this platform")
    assert ph_d["copy_ops"] < ph_u["copy_ops"], (
        f"profiled copy ops: donated {ph_d['copy_ops']} vs undonated "
        f"{ph_u['copy_ops']}")


def test_phase_api_reports_copy_fraction():
    """device_phases exposes copy_frac as a first-class metric for any
    step fn."""
    X, Y = _batch()
    _, step = _fresh(donate=True)
    ph = profiler.device_phases(lambda: step(X, Y), steps=2)
    if not ph:
        pytest.skip("no device trace available on this platform")
    assert set(ph) >= {"compute_ms", "collective_ms", "copy_ms",
                       "total_device_ms", "compute_ops", "copy_ops"}
    if ph["total_device_ms"] > 0:
        assert 0.0 <= ph["copy_frac"] <= 1.0
