"""Runner ``train``: ``DataLoader(use_device_prefetch=True)`` ->
``paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), AdamW)``
on one chip, default donation, the flash kernel chosen by the entry
point's own rule. A seeded set of fixed sequences is cycled in order;
the loss is fetched every ``fetch_every``-th step and at the end.
"""
from __future__ import annotations

import math
import statistics
import time

import numpy as np

from benchmark import program

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")
MAX_STEPS = 1 << 16


def cycled_loader(job, vocab, seed):
    from paddle_tpu.io import DataLoader, Dataset

    batch, seq, n = job["batch"], job["seq"], job["sequences"]
    toks = np.random.default_rng([seed, 2]).integers(
        0, vocab, (n, seq + 1)).astype(np.int32)

    class Cycled(Dataset):
        def __len__(self):
            return batch * MAX_STEPS

        def __getitem__(self, i):
            row = toks[i % n]
            return row[:-1], row[1:]

    return DataLoader(Cycled(), batch_size=batch, shuffle=False,
                      use_device_prefetch=True)


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    wl, model_cfg, say, spans = (ctx.workload, ctx.config, ctx.say,
                                 ctx.spans)
    job = wl["job"]
    model = program.build_lm(model_cfg, job["seq"], ctx.seed,
                             flash=wl.get("flash", True))
    opt = optimizer.AdamW(learning_rate=job["learning_rate"],
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    say(built_s=round(ctx.since_start(), 1), batch=job["batch"],
        seq=job["seq"])
    it = iter(cycled_loader(job, model_cfg["vocab_size"], ctx.seed))
    every = job["fetch_every"]
    fetched = []                    # (step number, loss)

    def one_step(n):
        with spans("train_step"):
            with spans("input"):
                t_in = time.perf_counter()
                xb, yb = next(it)
                waits.append(time.perf_counter() - t_in)
            with spans("dispatch"):
                loss = step(xb, yb)
            if n % every == 0:
                with spans("fetch"):
                    fetched.append((n, float(loss._data)))
        return loss, (xb, yb)

    try:
        # warm-up: compile or load the one program, then two more steps
        waits, n = [], 0
        programs_after_first = None
        for _ in range(job["warmup_steps"]):
            n += 1
            loss, last = one_step(n)
            fetched.append((n, float(loss._data)))
            if programs_after_first is None:
                programs_after_first = ctx.compiles.programs
        first_loss = fetched[0][1]

        waits, n0 = [], n
        setup_s = ctx.since_start()
        t0 = time.perf_counter()
        t1 = t0 + ctx.seconds
        trace_from = t1 - min(ctx.trace_seconds, ctx.seconds / 2)
        traced_at = None            # step count when the profiler started
        while time.perf_counter() < t1:
            n += 1
            loss, last = one_step(n)
            # the profiler starts at a fetch, when the device has drained
            if ctx.trace and traced_at is None and n % every == 0 \
                    and time.perf_counter() >= trace_from:
                ctx.start_trace()
                spans.open("traced")
                traced_at = n
        with spans("fetch"):
            final = float(loss._data)   # closes the window: device done
        elapsed = time.perf_counter() - t0
        if traced_at is not None:
            spans.close("traced")   # drained at both ends
            ctx.stop_trace()
        recompiled = ctx.compiles.programs - programs_after_first
    finally:
        it.close()                  # stops the prefetch thread

    steps = n - n0
    if fetched[-1][0] != n:
        fetched.append((n, final))
    xb, yb = last
    compiled = step._jitted.lower(
        1, *program.shapes_of((step._carry, [p._data for p in step._params],
                               step._slots,
                               [b._data for b in step._buffers],
                               step._lr_arr)),
        step._scaler_state, *program.shapes_of((xb._data, yb._data))
    ).compile()                     # the same program again: a cache hit
    text = compiled.as_text()
    kernels = {k: program.custom_calls(text, k) for k in KERNELS}
    say(flash_custom_calls={k: len(v) for k, v in kernels.items()},
        step_program_bytes=program.program_bytes(compiled))

    losses = [v for _, v in fetched]
    in_window = [v for s, v in fetched if s > n0]   # the final one included
    tail = losses[-8:]
    checks = {
        "no_recompile_after_step1": recompiled == 0,
        "losses_finite": all(math.isfinite(v) for v in losses),
        "first_loss_near_ln_vocab": abs(
            first_loss - math.log(model_cfg["vocab_size"])) < 1.0,
        "loss_fell": statistics.median(tail) < first_loss,
    }
    if wl.get("flash", True) is True:
        checks["flash_once_per_layer"] = all(
            len(v) == model_cfg["num_hidden_layers"]
            for v in kernels.values())
    say(steps=steps, elapsed_s=round(elapsed, 3), fetched=len(in_window),
        first_loss=round(first_loss, 4), last_losses=[round(v, 4)
                                                      for v in tail],
        recompiled_after_step1=recompiled,
        traced_steps=0 if traced_at is None else n - traced_at)
    samples = {
        "setup_s": setup_s,
        "window_s": elapsed,
        "steps": steps,
        "tokens": steps * job["batch"] * job["seq"],
        "input_wait_ms": [v * 1e3 for v in waits],
    }
    return {
        "checks": checks,
        "attempted": steps,
        # a loss is seen only where it is fetched: those are the steps
        # that can be told to have failed
        "failed": sum(1 for v in in_window if not math.isfinite(v)),
        "samples": samples,
        "trace_outer": "traced",
        "trace_iteration": "train_step",
        "kernels": kernels,
    }
