"""Benchmark entry point (driver contract).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

Primary metric (BASELINE.md north star, single-chip proxy for gate #4):
  ~1B-param GPT (Llama architecture) LM pretraining, whole step compiled,
  bf16 params/compute, Pallas flash attention — tokens/sec/chip and MFU.
  ``vs_baseline`` = measured MFU / 0.45 (the north-star ≥45%-MFU gate):
  >= 1.0 means the gate is met. This replaces the round-2 self-picked
  throughput bars, which VERDICT.md correctly called vanity ratios.

Also measured (reported in "extra"):
  ResNet-50 on CIFAR-10-shaped data, whole-step compiled — images/sec
  (BASELINE config #1), and the round-2 small-GPT config for continuity.

Timing notes: every timed region ends with a host fetch of the loss
(``float(loss)``), so the device queue has drained when the clock stops.

The default mode needs a TPU and fails without one; a failed phase fails
the run. Chip runs go through the builder's tool (see README); the quick
proof that the system starts on the chip is ``python chip_smoke.py``.
"""
from __future__ import annotations

import json
import os
import time

MFU_GATE = 0.45  # BASELINE gate #4: >= 45% MFU


def _timed_steps(step_fn, warmup=2, steps=10, windows=2):
    """Compile + warm up, then time `steps` steps; host-fetch the last
    loss to force the device queue to drain. Runs `windows` timed
    windows (each drained) and returns the BEST one's steps/sec — a
    best-of-N, not a median, and reported without its spread (ROADMAP
    S0 replaces this with medians and sample counts)."""
    for _ in range(warmup):
        float(step_fn()._data)
    best = 0.0
    for _ in range(windows):
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step_fn()
        float(loss._data)
        best = max(best, steps / (time.perf_counter() - t0))
    return best


def _resnet50_setup(batch=64):
    """One setup for BOTH resnet numbers so the k=32 and single-step
    figures measure the identical configuration."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    paddle.set_default_dtype("float32")
    model = resnet50(num_classes=10)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
    step = paddle.jit.TrainStep(model, nn.CrossEntropyLoss(), opt)
    rng = np.random.RandomState(0)
    X = paddle.to_tensor(rng.randn(batch, 3, 32, 32).astype(np.float32))
    Y = paddle.to_tensor(rng.randint(0, 10, (batch,)).astype(np.int64))
    return step, X, Y


def bench_resnet50(batch=64):
    step, X, Y = _resnet50_setup(batch)
    # ~1 ms of device work per step: dispatch-bound (BENCH_r05: 9,268
    # img/s at one step per dispatch vs 36,314 at 32), so use the
    # framework's k-steps-per-dispatch path (TrainStep.run_steps,
    # lax.scan) — numerics identical to k calls
    k = 32

    def kstep():
        return step.run_steps(k, X, Y)[-1]

    return _timed_steps(kstep, steps=4) * batch * k


def bench_gpt_small(batch=8, seq=512):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )

    paddle.seed(0)
    paddle.set_default_dtype("float32")
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=512, intermediate_size=1408,
        num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(cfg), opt)
    rng = np.random.RandomState(0)
    X = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    Y = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    k = 8  # ~8 ms steps: host dispatch is still a visible share

    def kstep():
        return step.run_steps(k, X, Y)[-1]

    sps = _timed_steps(kstep, steps=4) * k
    from paddle_tpu import profiler
    flops_per_token = 6 * n_params + 6 * cfg.num_hidden_layers * \
        cfg.hidden_size * seq
    mfu = profiler.estimate_mfu(flops_per_token * batch * seq, 1.0 / sps)
    return sps * batch * seq, mfu


def bench_gpt_1b(batch=4, seq=2048):
    """~0.95B-param Llama-architecture GPT, bf16, flash attention, no
    remat (fits v5e HBM at batch 4), AdamW. The chip-saturating config:
    measured 2026-07 on v5e (BENCH_r05) at ~22.4K tokens/s = ~69% MFU;
    older than the installed JAX and not re-measured since."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer, profiler
    from paddle_tpu.models.llama import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )

    paddle.seed(0)
    paddle.set_default_dtype("bfloat16")
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=seq,
        use_flash_attention=True)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(cfg), opt)
    rng = np.random.RandomState(0)
    X = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    Y = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    sps = _timed_steps(lambda: step(X, Y), steps=20)
    tokens_per_sec = sps * batch * seq
    # model FLOPs (PaLM accounting): 6N per token + causal attention
    # 12*L*h*s*0.5 per token; recompute is off so no remat multiplier
    flops_per_token = 6 * n_params + 6 * cfg.num_hidden_layers * \
        cfg.hidden_size * seq
    mfu = profiler.estimate_mfu(flops_per_token * batch * seq, 1.0 / sps)
    # per-phase device breakdown (xplane; VERDICT r4 #9) — compute vs
    # collective vs copy fractions of the measured step, via the public
    # profiler API (the copy_frac the donated-buffer + prefetch work
    # tracks round over round)
    phases = profiler.device_phases(lambda: step(X, Y), steps=3,
                                    warmup=0)  # already warm
    paddle.set_default_dtype("float32")
    return tokens_per_sec, mfu, n_params, phases


def bench_resnet50_single(batch=64):
    """HONEST single-step eager-dispatch number (no run_steps k-step
    amortization) — reported alongside the k=32 number so no quoted
    figure relies on an unstated measurement trick (VERDICT r4 #10).
    Also returns the phase breakdown of the same config (ResNet-50
    previously reported no copy-fraction at all)."""
    from paddle_tpu import profiler

    step, X, Y = _resnet50_setup(batch)
    img_s = _timed_steps(lambda: step(X, Y), steps=20, windows=3) * batch
    phases = profiler.device_phases(lambda: step(X, Y), steps=3,
                                    warmup=0)
    return img_s, phases


def bench_input_pipeline(batch=64, n_batches=16):
    """The loader regime the resident-X/Y numbers above exclude: a fresh
    host batch EVERY step. naive = to_tensor at use time (transfer
    serialized into the step); prefetched = io.prefetch_to_device
    (depth-2 double buffer, per-dtype coalesced staging, background
    thread) overlapping transfer with the previous step's compute.
    Reports images/sec for both and the overlap speedup."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.io import prefetch_to_device

    step, X, Y = _resnet50_setup(batch)
    rng = np.random.RandomState(1)
    data = [(rng.randn(batch, 3, 32, 32).astype(np.float32),
             rng.randint(0, 10, (batch,)).astype(np.int64))
            for _ in range(n_batches)]
    float(step(X, Y)._data)  # compile outside every timed window

    def run_naive():
        loss = None
        for xb, yb in data:
            loss = step(paddle.to_tensor(xb), paddle.to_tensor(yb))
        float(loss._data)

    def run_prefetched():
        loss = None
        for xb, yb in prefetch_to_device(data, depth=2):
            loss = step(xb, yb)
        float(loss._data)

    best = {}
    for name, fn in (("naive", run_naive), ("prefetched", run_prefetched)):
        fn()  # warm (first prefetched pass also compiles the unpack)
        dt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            fn()
            dt = min(dt, time.perf_counter() - t0)
        best[name] = batch * n_batches / dt
    return {
        "naive_images_per_sec": round(best["naive"], 1),
        "prefetched_images_per_sec": round(best["prefetched"], 1),
        "overlap_speedup": round(best["prefetched"] / best["naive"], 3),
    }


def bench_serving(tiny=False, n_requests=16, max_new_tokens=32,
                  max_num_seqs=8, seed=0):
    """Continuous-batching serving throughput (the paddle_tpu.serving
    engine): admit ``n_requests`` prompts of unequal lengths, stream
    them through the paged-KV engine to completion, report tokens/s,
    TTFT, TPOT and batch occupancy. A compile-warmup pass runs first so
    the measured window reports steady-state serving, not XLA compiles
    (the default engine is now the ragged single-shape step, so warmup
    compiles exactly one step function). ``tiny=True`` is the XLA:CPU
    smoke config the slow-marked tier test runs. A trailing comparison
    phase (ISSUE 9) runs one shared-prefix workload through a bucketed
    AND a ragged engine and reports the padding/prefix-cache/compile
    deltas as ``extra["ragged_comparison"]``. Two more trailing phases
    (ISSUE 11) trend the in-graph sampler and speculative decoding:
    ``extra["sampled_decode"]`` (seeded sampled requests, zero logits
    fetches asserted) and ``extra["speculative"]`` (self-draft k=3,
    acceptance counters + tokens/s vs the sampled baseline)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    if tiny:
        cfg = LlamaConfig.tiny()
        n_requests, max_new_tokens = min(n_requests, 10), min(
            max_new_tokens, 8)
    else:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = LLMEngine(model, EngineConfig(
        max_num_seqs=max_num_seqs,
        max_model_len=min(cfg.max_position_embeddings, 1024)))
    rng = np.random.RandomState(seed)
    sp = SamplingParams(max_new_tokens=max_new_tokens)

    def prompts(n, base):
        # unequal lengths across the batch — the ragged regime
        # continuous batching exists for
        return [list(rng.randint(0, cfg.vocab_size,
                                 size=base + 3 * (i % 5) + 1))
                for i in range(n)]

    # warmup: REPLAY the measured scenario's shape set — a full-width
    # admission wave plus the late-arrival wave — so every batch/seq
    # bucket (and the shrinking decode batches as requests drain)
    # compiles before the timed window
    for p in prompts(max(max_num_seqs, 5), 5):
        eng.add_request(p, sampling=sp)
    warm_late = []
    while eng.has_unfinished():
        eng.step()
        if not warm_late and eng.metrics.decode_steps >= 2:
            warm_late = [eng.add_request(p, sampling=sp)
                         for p in prompts(2, 4)]
    eng.reset_metrics()

    t0 = time.perf_counter()
    for p in prompts(n_requests - 2, 5):
        eng.add_request(p, sampling=sp)
    # two late arrivals join the running batch mid-flight
    late = []
    while eng.has_unfinished():
        eng.step()
        if not late and eng.metrics.decode_steps >= 2:
            late = [eng.add_request(p, sampling=sp)
                    for p in prompts(2, 4)]
    dt = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    assert snap["num_finished"] == n_requests, snap

    # resilience smoke (ISSUE 6): a SEPARATE small-cache engine runs
    # swap-based preemption under genuine OOM and then a graceful
    # drain, so the BENCH_serving JSON trends the new serving/*
    # resilience counters with nonzero traffic — the measured
    # throughput window above is untouched.
    r_eng = LLMEngine(model, EngineConfig(
        block_size=4, num_blocks=10, max_num_seqs=4, max_model_len=32,
        swap_mode="host"))
    r_sp = SamplingParams(max_new_tokens=8)
    for p in prompts(4, 6):
        r_eng.add_request(list(p), sampling=r_sp)
    while r_eng.has_unfinished():
        r_eng.step()
    # second wave: 6 requests on a 4-seq engine, drained after two
    # steps — some finish within grace, the queued ones abort
    for p in prompts(6, 6):
        r_eng.add_request(list(p), sampling=r_sp)
    for _ in range(2):
        r_eng.step()
    r_eng.drain(grace_s=30.0)
    r_snap = r_eng.metrics.snapshot()
    assert r_snap["serving_swapped_out"] > 0, r_snap
    assert r_snap["serving_drain_completed"] == 1, r_snap
    resilience = {k: v for k, v in r_snap.items()
                  if k.startswith("serving_") or k == "preemptions"}

    # ragged hot-path comparison (ISSUE 9): the SAME shared-prefix
    # two-wave workload through a bucketed engine and a ragged one
    # (single compiled step + chunked prefill + COW prefix cache).
    # Wave 1 is each engine's compile warmup; wave 2 is timed, and on
    # the ragged engine its re-sent shared prefix takes real COW
    # prefix-cache hits. Token parity is asserted first, so the
    # speedup column never compares different outputs.
    cmp_rng = np.random.RandomState(seed + 1)
    shared = list(cmp_rng.randint(0, cfg.vocab_size, size=24))
    cmp_prompts = [
        shared + list(cmp_rng.randint(0, cfg.vocab_size, size=8)),
        list(cmp_rng.randint(0, cfg.vocab_size, size=3)),
        shared + list(cmp_rng.randint(0, cfg.vocab_size, size=5)),
        list(cmp_rng.randint(0, cfg.vocab_size, size=6)),
    ]
    cmp_sp = [
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=5, temperature=0.8, seed=7),
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=4),
    ]

    def run_cmp(ragged):
        e = LLMEngine(model, EngineConfig(
            block_size=4, max_num_seqs=4, max_model_len=64,
            max_batched_tokens=16,   # < the long prompts: forces chunks
            ragged=ragged, chunked_prefill=ragged, prefix_cache=ragged))
        outs, dt_wave = [], 0.0
        for wave in range(2):
            rids = [e.add_request(p, sampling=s)
                    for p, s in zip(cmp_prompts, cmp_sp)]
            t = time.perf_counter()
            while e.has_unfinished():
                e.step()
            dt_wave = time.perf_counter() - t   # keep wave 2's time
            outs.append([e.get_request(r).generated for r in rids])
        return e, outs, dt_wave

    c_eng_r, c_outs_r, c_dt_r = run_cmp(True)
    c_eng_b, c_outs_b, c_dt_b = run_cmp(False)
    assert c_outs_r == c_outs_b, "ragged != bucketed token streams"
    c_snap_r = c_eng_r.metrics.snapshot()
    c_snap_b = c_eng_b.metrics.snapshot()
    assert c_snap_r["padded_token_frac"] == 0.0, c_snap_r
    assert c_snap_b["padded_token_frac"] > 0.0, c_snap_b
    assert c_snap_r["serving_prefix_cache_hits"] > 0, c_snap_r
    assert len(c_eng_r._seen_shapes) == 1, c_eng_r._seen_shapes
    c_gen = sum(len(toks) for toks in c_outs_r[1])
    ragged_cmp = {
        "ragged_tokens_per_sec": round(c_gen / c_dt_r, 2),
        "bucketed_tokens_per_sec": round(c_gen / c_dt_b, 2),
        "ragged_vs_bucketed": round(c_dt_b / c_dt_r, 3),
        "ragged_compiled_step_shapes": len(c_eng_r._seen_shapes),
        "bucketed_compiled_step_shapes": len(c_eng_b._seen_shapes),
        "ragged_padded_token_frac": c_snap_r["padded_token_frac"],
        "bucketed_padded_token_frac": c_snap_b["padded_token_frac"],
        "prefix_cache_hits": c_snap_r["serving_prefix_cache_hits"],
        "prefix_cache_hit_tokens":
            c_snap_r["serving_prefix_cache_hit_tokens"],
        "prefill_chunks": c_snap_r["serving_prefill_chunks"],
        "mixed_steps": c_snap_r["mixed_steps"],
    }

    # in-graph sampled decode (ISSUE 11): seeded sampled requests
    # through the fused device sampler — the step fetches B packed int32
    # rows, never logits (asserted, so the bench can't silently regress
    # to host sampling). Wave 1 warms the compile, wave 2 is timed.
    smp_rng = np.random.RandomState(seed + 2)
    s_prompts = [list(smp_rng.randint(0, cfg.vocab_size, size=5 + i % 4))
                 for i in range(6)]
    s_sp = [SamplingParams(max_new_tokens=8, temperature=0.8, top_p=0.9,
                           seed=50 + i) for i in range(len(s_prompts))]
    s_eng = LLMEngine(model, EngineConfig(
        block_size=4, max_num_seqs=4, max_model_len=64))
    s_dt, s_gen = 0.0, 0
    for wave in range(2):
        if wave:
            s_eng.reset_metrics()   # wave 1 was compile warmup
        rids = [s_eng.add_request(list(p), sampling=s)
                for p, s in zip(s_prompts, s_sp)]
        t = time.perf_counter()
        while s_eng.has_unfinished():
            s_eng.step()
        s_dt = time.perf_counter() - t   # keep wave 2's time
        s_gen = sum(len(s_eng.get_request(r).generated) for r in rids)
    assert s_eng.num_logits_fetches == 0, "sampled decode fetched logits"
    s_snap = s_eng.metrics.snapshot()
    sampled_cmp = {
        "tokens_per_sec": round(s_gen / s_dt, 2),
        "tpot_ms_avg": s_snap["tpot_ms_avg"],
        "sampled_steps": s_eng.num_sampled_steps,
        "logits_fetches": s_eng.num_logits_fetches,
    }

    # speculative decoding (ISSUE 11): the same sampled workload plus a
    # draft proposing k tokens per decode row, verified inside the one
    # ragged step. Random-init weights have no distilled draft, so the
    # target drafts for ITSELF — that pins the mechanism end-to-end and
    # trends the acceptance counters at their upper bound (a greedy
    # self-draft verifies ~everything; sampled rows reject whatever the
    # temperature disagrees with).
    k_eng = LLMEngine(model, EngineConfig(
        block_size=4, max_num_seqs=4, max_model_len=64,
        draft_model=model, num_spec_tokens=3))
    k_dt, k_gen = 0.0, 0
    for wave in range(2):
        if wave:
            k_eng.reset_metrics()   # wave 1 was compile warmup
        rids = [k_eng.add_request(list(p), sampling=s)
                for p, s in zip(s_prompts, s_sp)]
        t = time.perf_counter()
        while k_eng.has_unfinished():
            k_eng.step()
        k_dt = time.perf_counter() - t   # keep wave 2's time
        k_gen = sum(len(k_eng.get_request(r).generated) for r in rids)
    assert k_eng.num_logits_fetches == 0, "spec decode fetched logits"
    assert k_eng.num_spec_proposed > 0
    k_snap = k_eng.metrics.snapshot()
    spec_cmp = {
        "tokens_per_sec": round(k_gen / k_dt, 2),
        "tpot_ms_avg": k_snap["tpot_ms_avg"],
        "num_spec_tokens": 3,
        "spec_proposed": k_eng.num_spec_proposed,
        "spec_accepted": k_eng.num_spec_accepted,
        "spec_acceptance_rate": round(k_eng.spec_acceptance_rate, 4),
        "vs_sampled_decode": round(s_dt / k_dt, 3),
        "logits_fetches": k_eng.num_logits_fetches,
    }

    return {
        "metric": "serving_tokens_per_sec",
        "value": round(snap["num_generated_tokens"] / dt, 2),
        "unit": "tokens/sec",
        # occupancy is the continuous-batching figure of merit: how full
        # the decode batch stays while requests churn
        "vs_baseline": snap["batch_occupancy"],
        "extra": {
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" n_req={n_requests} max_new={max_new_tokens}"
                      f" max_num_seqs={max_num_seqs}",
            "wall_s": round(dt, 3),
            **snap,
            "resilience_smoke": resilience,
            "ragged_comparison": ragged_cmp,
            "sampled_decode": sampled_cmp,
            "speculative": spec_cmp,
        },
    }


def _fleet_model_cfg(tiny):
    from paddle_tpu.models.llama import LlamaConfig

    if tiny:
        return LlamaConfig.tiny()
    return LlamaConfig(
        vocab_size=32000, hidden_size=512, intermediate_size=1408,
        num_hidden_layers=8, num_attention_heads=8,
        num_key_value_heads=8, max_position_embeddings=1024)


def _fleet_prefix_workload(model, cfg, make_ecfg, replicas, seed):
    """Multi-tenant shared-prefix serving through the fleet: four
    tenants behind one shared system header (4 blocks) with per-tenant
    headers (2 blocks) and FIXED-length tails, submitted in waves so
    advertisements exist before later dispatches. The identical
    workload runs twice — prefix-affine routing vs load-only — and the
    comparison reports the fleet-wide hit rate
    (``prefix_cache_hit_tokens / prompt_tokens``) over the wave
    window, plus client-side TTFT from SERIAL probes after the waves —
    one request in flight at a time, identical prompt length, the only
    difference being whether the prefix is cached where the request
    lands. (Wave-level TTFT would confound the comparison: affinity
    concentrates a wave onto one replica, whose admission budget then
    serializes it.) Greedy decoding, so both modes must emit
    bit-identical tokens — routing policy may move work, never change
    it."""
    import numpy as np

    from paddle_tpu.serving import SamplingParams
    from paddle_tpu.serving.fleet import (
        FleetConfig, FleetRouter, InProcessReplica,
    )

    rng = np.random.RandomState(seed + 7)
    bs = make_ecfg().block_size
    tail_len = 8
    system = list(map(int, rng.randint(1, cfg.vocab_size, size=4 * bs)))
    tenants = {f"tenant{k}": list(map(int, rng.randint(
        1, cfg.vocab_size, size=2 * bs))) for k in range(4)}
    plen = len(system) + 2 * bs + tail_len
    # waves of 6 random-tenant requests: load-only's round-robin
    # placement can't accidentally track tenant->home-replica affinity
    names = sorted(tenants)
    waves = []
    for _ in range(3):
        wave = []
        for _ in range(6):
            t = names[int(rng.randint(0, len(names)))]
            tail = list(map(int, rng.randint(1, cfg.vocab_size,
                                             size=tail_len)))
            wave.append((t, system + tenants[t] + tail))
        waves.append(wave)
    # waves OVERLAP in flight (a wave is submitted while the previous
    # one still decodes), so load-only routing genuinely balances by
    # occupancy instead of degenerating to always-min-id on an idle
    # fleet; seats cover two waves so affinity's concentration is
    # never forced to spill for seats
    seats = 2 * len(waves[0])
    warm_prompts = [list(map(int, rng.randint(1, cfg.vocab_size,
                                              size=plen)))
                    for _ in range(replicas * seats)]
    # serial TTFT probes: repeats of wave prompts (cache-hit path) vs
    # fresh never-seen prompts of the SAME length (cold path)
    hit_probes = [waves[-1][j] for j in range(3)]
    cold_probes = [
        (f"probe{j}", list(map(int, rng.randint(1, cfg.vocab_size,
                                                size=plen))))
        for j in range(3)]

    def run(fleet_cfg):
        # a bounded per-step token budget (4 blocks) makes prefill cost
        # proportional to COMPUTED tokens: a cold prompt chunks over
        # ceil(plen/budget) ragged steps while a deep prefix hit
        # prefills its short suffix in one — without this the fixed
        # ragged shape makes cold and hit prefills cost the same step
        router = FleetRouter(
            [InProcessReplica(model,
                              make_ecfg(max_num_seqs=seats,
                                        max_batched_tokens=4 * bs),
                              replica_id=f"x{i}")
             for i in range(replicas)], fleet_cfg)
        # compile-only warmup: unrelated prompts of the same bucketed
        # shapes, run TWICE — the repeat prefix-hits its own first
        # pass, so the batched cache-hit prefill shapes compile here
        for _ in range(2):
            for p in warm_prompts:
                router.add_request(p, sampling=SamplingParams(
                    max_new_tokens=tail_len))
            while router.has_unfinished():
                router.step()
        # single-row warmup directly on EVERY engine: one serial
        # prefill and its repeat (which prefix-hits), so the probe
        # phase never measures compilation on either replica
        for i, h in enumerate(router.replicas):
            p = list(map(int, rng.randint(1, cfg.vocab_size,
                                          size=plen)))
            for k in range(2):
                h.engine.add_request(f"sw{i}-{k}", p,
                                     sampling=SamplingParams(
                                         max_new_tokens=tail_len))
                while h.engine.has_unfinished():
                    h.engine.step()
        base_hit = sum(h.engine.block_manager.num_prefix_hit_tokens
                       for h in router.replicas)
        base_computed = sum(h.engine.metrics.num_prompt_tokens
                            for h in router.replicas)
        t_sub, ttft = {}, {}

        def cb(rid, token, finished):
            if rid not in ttft:
                ttft[rid] = time.perf_counter() - t_sub[rid]

        gen = {}
        all_ids = []
        for w, wave in enumerate(waves):
            for j, (t, p) in enumerate(wave):
                rid = f"w{w}-{j}"
                all_ids.append(rid)
                router.add_request(rid, p, sampling=SamplingParams(
                    max_new_tokens=tail_len, tenant_id=t))
            if w + 1 < len(waves):
                # a few steps, NOT a drain: the next wave arrives while
                # this one still decodes (prefill is done, so its
                # prefixes are committed and advertised)
                for _ in range(12):
                    router.step()
        while router.has_unfinished():
            router.step()
        for rid in all_ids:
            gen[rid] = list(router.release_request(rid).generated)
        # hit rate over the wave window only (warmup repeats
        # prefix-hit their own first pass by design)
        hit = sum(h.engine.block_manager.num_prefix_hit_tokens
                  for h in router.replicas) - base_hit
        computed = sum(h.engine.metrics.num_prompt_tokens
                       for h in router.replicas) - base_computed
        # serial TTFT probes: one request in flight at a time, so the
        # cold/hit difference is cached-vs-computed prefill and
        # nothing else. Ships are off during probes — a mid-probe
        # ship would bill its one-time gather/scatter compile to
        # whichever probe it interrupted
        router.cfg.prefix_ship = False
        probe_ms = {}
        for kind, plist in (("cold", cold_probes), ("hit", hit_probes)):
            ts = []
            for j, (t, p) in enumerate(plist):
                rid = f"{kind}-{j}"
                t_sub[rid] = time.perf_counter()
                router.add_request(rid, p, sampling=SamplingParams(
                    max_new_tokens=tail_len, tenant_id=t), callback=cb)
                while router.has_unfinished():
                    router.step()
                gen[rid] = list(router.release_request(rid).generated)
                ts.append(ttft[rid])
            probe_ms[kind] = round(1e3 * sum(ts) / len(ts), 3)
        snap = router.snapshot()
        return gen, {
            "fleet_prefix_hit_rate": round(hit / (hit + computed), 4)
                if hit + computed else 0.0,
            "ttft_cold_ms": probe_ms["cold"],
            "ttft_hit_ms": probe_ms["hit"],
            "prefix_affine_dispatches":
                snap["fleet_prefix_affine_dispatches"],
            "prefix_ships": snap["fleet_prefix_ships"],
            "prefix_ship_bytes": snap["fleet_prefix_ship_bytes"],
            "prefix_hit_tokens_advertised":
                snap["fleet_prefix_hit_tokens"],
        }

    gen_a, affine = run(FleetConfig(prefix_ship_threshold=2))
    gen_l, load_only = run(FleetConfig(prefix_affinity=False,
                                       prefix_ship=False))
    assert gen_a == gen_l, "routing policy changed tokens"
    # the acceptance pins: affinity strictly beats load-only on fleet
    # hit rate, and cache-hit TTFT beats cold TTFT at equal length
    assert (affine["fleet_prefix_hit_rate"]
            > load_only["fleet_prefix_hit_rate"]), (affine, load_only)
    assert affine["ttft_hit_ms"] < affine["ttft_cold_ms"], affine
    return {
        "prompt_len": plen,
        "shared_tokens": len(system),
        "tenant_tokens": 2 * bs,
        "n_requests": sum(len(w) for w in waves),
        "affine": affine,
        "load_only": load_only,
    }


def _worker_model_small(spec):
    """WorkerSpec factory (``model="bench:_worker_model_small"``) so
    subprocess bench workers build the exact gpt-small twin of the
    in-process replicas — same seed, same weights, comparable runs."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(int(spec.get("seed", 0)))
    paddle.set_default_dtype("float32")
    model = LlamaForCausalLM(_fleet_model_cfg(False))
    model.eval()
    return model


def bench_fleet(tiny=False, replicas=2, n_requests=16,
                max_new_tokens=32, max_num_seqs=4, seed=0,
                subprocess_mode=False, disagg=False):
    """Multi-replica serving throughput through the FleetRouter
    (``--serving --replicas N``): the same ragged-prompt scenario as
    :func:`bench_serving`, dispatched across ``replicas`` engines
    sharing one set of weights. After the measured window, a SEPARATE
    resilience pass drains one replica of a zero-grace pair mid-run so
    the BENCH JSON trends the fleet counters (hand-offs, replica
    deaths) with nonzero traffic.

    ``--subprocess`` re-runs the measured window through a
    :class:`ReplicaSupervisor` fleet of worker PROCESSES behind the
    length-prefixed RPC transport — same prompts, same weights — and
    reports tokens/s, aggregate RPC overhead (calls, wire time), and a
    SIGKILL-one-worker-mid-run smoke alongside the in-process numbers.

    ``--disagg`` splits the fleet into prefill and decode roles (first
    half prefill) so every measured request prefills on one side and is
    KV-SHIPPED to the other for decode — zero prompt tokens recomputed.
    The extra then carries the ship counters (requests/blocks/bytes/
    ms_avg) plus a recompute-path comparison against the previous
    round's undisaggregated fleet number when BENCH_serving_r05.json is
    on disk; the subprocess SIGKILL smoke targets a DECODE worker so
    the JSON also trends the crash→recompute-fallback path."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, SamplingParams
    from paddle_tpu.serving.fleet import (
        FleetConfig, FleetRouter, InProcessReplica,
    )
    from paddle_tpu.testing import faults

    if subprocess_mode and jax.default_backend() != "cpu":
        # one process per chip: this function builds an in-process model
        # (which takes the chip), then every worker it spawns inherits
        # the same device environment and needs the same chip — the
        # second process fails or hangs. Nothing places workers on
        # separate chips yet (ROADMAP R5).
        raise SystemExit(
            f"bench.py --subprocess refuses to start on the "
            f"{jax.default_backend()!r} backend: a chip belongs to one "
            f"process at a time and this parent already holds it, so the "
            f"worker processes could never reach it. Run the subprocess "
            f"fleet with JAX_PLATFORMS=cpu until replicas can be placed "
            f"one per chip (ROADMAP R5).")
    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    cfg = _fleet_model_cfg(tiny)
    if tiny:
        n_requests, max_new_tokens = min(n_requests, 12), min(
            max_new_tokens, 8)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def ecfg(**kw):
        kw.setdefault("max_num_seqs", max_num_seqs)
        kw.setdefault("max_model_len",
                      min(cfg.max_position_embeddings, 1024))
        return EngineConfig(**kw)

    n_pre = max(1, replicas // 2) if disagg else 0
    roles = ({f"r{i}": ("prefill" if i < n_pre else "decode")
              for i in range(replicas)} if disagg else None)
    router = FleetRouter(
        [InProcessReplica(model, ecfg(), replica_id=f"r{i}")
         for i in range(replicas)],
        FleetConfig(roles=roles) if roles else None)
    rng = np.random.RandomState(seed)
    sp = SamplingParams(max_new_tokens=max_new_tokens)

    def prompts(n, base):
        return [list(rng.randint(0, cfg.vocab_size,
                                 size=base + 3 * (i % 5) + 1))
                for i in range(n)]

    # warmup: fill every replica past its seat count so all bucketed
    # shapes (and the shrinking decode batches) compile per engine
    for p in prompts(replicas * max_num_seqs + 2, 5):
        router.add_request(p, sampling=sp)
    while router.has_unfinished():
        router.step()
    tokens0 = router.num_tokens_emitted

    measured_prompts = prompts(n_requests, 5)
    t0 = time.perf_counter()
    rids = [router.add_request(p, sampling=sp)
            for p in measured_prompts]
    while router.has_unfinished():
        router.step()
    dt = time.perf_counter() - t0
    tokens = router.num_tokens_emitted - tokens0
    assert all(router.get_request(r).finish_reason == "length"
               for r in rids)
    snap = router.snapshot()
    if disagg:
        # every request prefilled on one side and decoded on the other
        # with its blocks shipped, not recomputed
        assert snap["fleet_kv_ship_requests"] >= n_requests, snap
        assert snap["fleet_kv_ship_bytes"] > 0, snap
        assert snap["fleet_recompute_fallbacks"] == 0, snap
        assert snap["fleet_tokens_recomputed"] == 0, snap

    # resilience smoke: zero-grace pair, one replica drained mid-run by
    # the fleet.drain_replica fault — every request must still finish
    # 'length' (hand-off invisible, resume-by-recompute)
    r_router = FleetRouter([
        InProcessReplica(model, ecfg(drain_grace_s=0.0),
                         replica_id=f"d{i}") for i in range(2)])
    r_rids = [r_router.add_request(p, sampling=SamplingParams(
        max_new_tokens=8)) for p in prompts(6, 6)]
    faults.install("fleet.drain_replica:flag:d0@3*1")
    try:
        while r_router.has_unfinished():
            r_router.step()
    finally:
        faults.clear()
    assert all(r_router.get_request(r).finish_reason == "length"
               for r in r_rids)
    assert r_router.num_handoffs > 0
    r_snap = r_router.snapshot()
    resilience = {k: v for k, v in r_snap.items()
                  if k.startswith("fleet_") and k != "fleet_tenants"}

    # out-of-process pass: same measured prompts through subprocess
    # workers, so tokens/s here vs above IS the RPC overhead
    sub = None
    if subprocess_mode:
        import tempfile

        from paddle_tpu.serving.fleet import (
            ReplicaSupervisor, SupervisorConfig, WorkerSpec,
        )

        sup = ReplicaSupervisor(
            WorkerSpec(model=("tiny_llama" if tiny
                              else "bench:_worker_model_small"),
                       seed=seed,
                       engine=dict(
                           max_num_seqs=max_num_seqs,
                           max_model_len=min(
                               cfg.max_position_embeddings, 1024))),
            SupervisorConfig(
                store_dir=tempfile.mkdtemp(prefix="bench_fleet_hb_")))
        try:
            s_handles = [
                sup.spawn(role=(("prefill" if i < n_pre else "decode")
                                if disagg else None))
                for i in range(replicas)]
            s_router = FleetRouter(s_handles, registry=sup.registry)
            sup.router = s_router
            for p in prompts(replicas * max_num_seqs + 2, 5):
                s_router.add_request(p, sampling=sp)
            while s_router.has_unfinished():
                s_router.step()
            s_tokens0 = s_router.num_tokens_emitted
            # RPC stats diffed across the window: boot pings and
            # warmup compiles would otherwise dominate ms-per-call
            rpc0 = [dict(h.rpc_stats) for h in sup.handles()]
            t1 = time.perf_counter()
            s_rids = [s_router.add_request(p, sampling=sp)
                      for p in measured_prompts]
            while s_router.has_unfinished():
                s_router.step()
            s_dt = time.perf_counter() - t1
            s_tokens = s_router.num_tokens_emitted - s_tokens0
            assert all(s_router.get_request(r).finish_reason == "length"
                       for r in s_rids)
            rpc = {"calls": 0, "retries": 0, "timeouts": 0,
                   "rpc_time_s": 0.0}
            for h, before in zip(sup.handles(), rpc0):
                for k in rpc:
                    rpc[k] += h.rpc_stats.get(k, 0) - before.get(k, 0)

            # resilience, subprocess edition: SIGKILL one worker
            # mid-run; every request must still finish 'length' on the
            # peer (transport-cached RNG, router hand-off). In disagg
            # mode the victim is a DECODE worker, so its shipped
            # requests exercise the crash→recompute-fallback path.
            victim = s_handles[n_pre] if disagg else s_handles[0]
            faults.install("fleet.worker_kill:flag:"
                           f"{victim.replica_id}@3*1")
            k_rids = [s_router.add_request(p, sampling=SamplingParams(
                max_new_tokens=8)) for p in prompts(6, 6)]
            try:
                while s_router.has_unfinished():
                    s_router.step()
            finally:
                faults.clear()
            assert all(s_router.get_request(r).finish_reason == "length"
                       for r in k_rids)
            sub = {
                "tokens_per_sec": round(s_tokens / s_dt, 2),
                "wall_s": round(s_dt, 3),
                "vs_inprocess": round((s_tokens / s_dt)
                                      / (tokens / dt), 3),
                "rpc_calls": rpc["calls"],
                "rpc_retries": rpc["retries"],
                "rpc_timeouts": rpc["timeouts"],
                "rpc_wire_s": round(rpc["rpc_time_s"], 3),
                "rpc_ms_per_call": round(
                    1e3 * rpc["rpc_time_s"] / max(rpc["calls"], 1), 3),
                "sigkill_smoke": {
                    "num_handoffs": s_router.num_handoffs,
                    "num_replicas_dead": s_router.num_replicas_dead,
                    "finished_length": len(k_rids),
                },
                **({"kv_ship_requests": s_router.num_kv_ship_requests,
                    "kv_ship_bytes": s_router.num_kv_ship_bytes,
                    "tokens_recomputed": s_router.num_tokens_recomputed,
                    "recompute_fallbacks":
                        s_router.num_recompute_fallbacks}
                   if disagg else {}),
            }
        finally:
            sup.shutdown()

    # fleet-global prefix cache: the multi-tenant shared-prefix
    # comparison (prefix-affine vs load-only routing) — the numbers
    # BENCH_serving_r07 records
    prefix_extra = None
    if not disagg:
        prefix_extra = _fleet_prefix_workload(model, cfg, ecfg,
                                              replicas, seed)

    disagg_extra = None
    if disagg:
        disagg_extra = {
            "n_prefill": n_pre, "n_decode": replicas - n_pre,
            "bytes_shipped": snap["fleet_kv_ship_bytes"],
            "blocks_shipped": snap["fleet_kv_ship_blocks"],
            "ship_requests": snap["fleet_kv_ship_requests"],
            "ship_ms_avg": snap["fleet_kv_ship_ms_avg"],
            "tokens_recomputed": snap["fleet_tokens_recomputed"],
            "recompute_fallbacks": snap["fleet_recompute_fallbacks"],
        }
        if os.path.exists("BENCH_serving_r05.json"):
            # r05 ran the identical scenario with role-less replicas
            # (resume-by-recompute fleet) — the ratio IS the cost/win
            # of disaggregation on this box
            with open("BENCH_serving_r05.json") as f:
                prev = json.load(f)
            disagg_extra["vs_r05_recompute_fleet"] = {
                "tokens_per_sec_ratio": round(
                    (tokens / dt) / prev["value"], 3),
                "r05_tokens_per_sec": prev["value"],
            }

    return {
        "metric": "fleet_tokens_per_sec",
        "value": round(tokens / dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": replicas,
        "extra": {
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" replicas={replicas} n_req={n_requests}"
                      f" max_new={max_new_tokens}"
                      f" max_num_seqs={max_num_seqs}"
                      + (" disagg" if disagg else ""),
            "wall_s": round(dt, 3),
            **{k: v for k, v in snap.items() if k != "replicas"},
            "resilience_smoke": resilience,
            **({"prefix": prefix_extra} if prefix_extra else {}),
            **({"disagg": disagg_extra} if disagg_extra else {}),
            **({"subprocess": sub} if sub is not None else {}),
        },
    }


def bench_peer(tiny=False, replicas=4, n_requests=16,
               max_new_tokens=32, max_num_seqs=4, seed=0):
    """Peer data plane vs router relay (``--serving --peer``): the
    disaggregated scenario of :func:`bench_fleet` — first half prefill,
    second half decode, every request's KV shipped across the role
    boundary — run TWICE over the same prompts and weights. The peer
    variant brings up a :class:`PeerListener` per replica and ships
    every block worker↔worker under router-issued tickets (zero KV
    payload bytes through the router, asserted); the relay variant
    pins ``peer_data_plane=False`` so the router itself carries every
    byte (the pre-peer path, still the ladder's middle rung). The
    primary value is peer-path tokens/s; ``vs_baseline`` is the relay
    number, so the ratio IS the control/data-plane split's cost or win
    on this box. Token streams must match between variants."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, SamplingParams
    from paddle_tpu.serving.fleet import (
        FleetConfig, FleetRouter, InProcessReplica,
    )

    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    cfg = _fleet_model_cfg(tiny)
    if tiny:
        n_requests, max_new_tokens = min(n_requests, 12), min(
            max_new_tokens, 8)
    model = LlamaForCausalLM(cfg)
    model.eval()

    n_pre = max(1, replicas // 2)
    roles = {f"r{i}": ("prefill" if i < n_pre else "decode")
             for i in range(replicas)}
    sp = SamplingParams(max_new_tokens=max_new_tokens)

    def run(peer):
        reps = [InProcessReplica(
            model, EngineConfig(
                max_num_seqs=max_num_seqs,
                max_model_len=min(cfg.max_position_embeddings, 1024)),
            replica_id=f"r{i}") for i in range(replicas)]
        if peer:
            for r in reps:
                r.start_peer()
        router = FleetRouter(reps, FleetConfig(
            roles=roles, peer_data_plane=peer))
        rng = np.random.RandomState(seed)

        def prompts(n, base):
            return [list(rng.randint(0, cfg.vocab_size,
                                     size=base + 3 * (i % 5) + 1))
                    for i in range(n)]

        # warmup compiles every bucketed shape on both roles
        for p in prompts(replicas * max_num_seqs + 2, 5):
            router.add_request(p, sampling=sp)
        while router.has_unfinished():
            router.step()
        tokens0 = router.num_tokens_emitted

        t0 = time.perf_counter()
        rids = [router.add_request(p, sampling=sp)
                for p in prompts(n_requests, 5)]
        while router.has_unfinished():
            router.step()
        dt = time.perf_counter() - t0
        tokens = router.num_tokens_emitted - tokens0
        assert all(router.get_request(r).finish_reason == "length"
                   for r in rids)
        snap = router.snapshot()
        # both variants ship every measured request's blocks — nothing
        # recomputed on either path
        assert snap["fleet_kv_ship_requests"] >= n_requests, snap
        assert snap["fleet_recompute_fallbacks"] == 0, snap
        assert snap["fleet_tokens_recomputed"] == 0, snap
        assert snap["fleet_tickets_issued"] == sum(
            router.ticket_outcomes.values()), snap
        if peer:
            # steady state: the payload NEVER touches the router
            assert snap["fleet_relay_bytes"] == 0, snap
            assert snap["fleet_peer_ship_bytes"] > 0, snap
        else:
            assert snap["fleet_tickets_issued"] == 0, snap
            assert snap["fleet_relay_bytes"] > 0, snap
        gen = [list(router.get_request(r).generated) for r in rids]
        for r in reps:
            r.close_peer()
        return gen, {
            "tokens_per_sec": round(tokens / dt, 2),
            "wall_s": round(dt, 3),
            "ship_requests": snap["fleet_kv_ship_requests"],
            "ship_blocks": snap["fleet_kv_ship_blocks"],
            "ship_bytes": snap["fleet_kv_ship_bytes"],
            "ship_ms_avg": snap["fleet_kv_ship_ms_avg"],
            "peer_ship_bytes": snap["fleet_peer_ship_bytes"],
            "router_relay_bytes": snap["fleet_relay_bytes"],
            "tickets_issued": snap["fleet_tickets_issued"],
            "ticket_outcomes": snap["fleet_ticket_outcomes"],
        }

    gen_p, peer = run(peer=True)
    gen_r, relay = run(peer=False)
    assert gen_p == gen_r, "peer/relay token streams diverged"

    return {
        "metric": "peer_data_plane_tokens_per_sec",
        "value": peer["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": relay["tokens_per_sec"],
        "extra": {
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" replicas={replicas} disagg {n_pre}p/"
                      f"{replicas - n_pre}d n_req={n_requests}"
                      f" max_new={max_new_tokens}"
                      f" max_num_seqs={max_num_seqs}",
            "peer": peer,
            "relay": relay,
        },
    }


def bench_routers(tiny=False, routers=2, n_requests=24,
                  max_new_tokens=8, max_num_seqs=8, seed=0):
    """Replicated control plane (``--serving --routers N``), two parts:

    1. **real engines** — ``routers`` FleetRouters over 4 shared
       in-process replicas, tenant-partitioned requests with every
       in-flight request holding a store lease. Three step rounds in,
       the router owning the most leased work is killed through the
       ``fleet.router_kill`` fault; the survivors adopt its leases and
       finish everything. Reports dispatches/s per router and the
       client-observed TTFT distribution (the p99 carries the
       router-TTL adoption stall — the cost of a control-plane death),
       against a single-router no-kill baseline of the same workload.
    2. **simulator** — a 100-replica, 3-router :class:`FleetSim` under
       a spike trace with a ``LoadThresholdPolicy`` autoscaler
       (``low=0.0``: scale-down is forbidden, draining shared sim
       handles would strand peer routers' work). Reports sim
       dispatches per wall second and the ``scale_to`` decisions the
       spike provoked; :meth:`FleetSim.check` enforces the exactness
       invariants before anything is reported."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.replica_registry import (
        MemStore, ReplicaRegistry,
    )
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, SamplingParams
    from paddle_tpu.serving.fleet import (
        Arrival, FleetConfig, FleetRouter, FleetSim, InProcessReplica,
        LeaseStore, LoadThresholdPolicy, spike_trace, tenant_home,
    )
    from paddle_tpu.testing import faults

    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    cfg = _fleet_model_cfg(tiny)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def ecfg(**kw):
        kw.setdefault("max_num_seqs", max_num_seqs)
        kw.setdefault("max_model_len",
                      min(cfg.max_position_embeddings, 1024))
        return EngineConfig(**kw)

    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(0, cfg.vocab_size,
                                size=5 + 3 * (i % 5) + 1))
               for i in range(n_requests)]
    tenants = [f"t{i % 8}" for i in range(n_requests)]

    def sp(tenant):
        return SamplingParams(max_new_tokens=max_new_tokens,
                              tenant_id=tenant)

    handles = [InProcessReplica(model, ecfg(), replica_id=f"r{i}")
               for i in range(4)]
    # warmup through a throwaway classic router: compile every bucketed
    # shape per engine before anything is timed
    warm = FleetRouter(handles)
    for p in prompts[:4 * max_num_seqs + 2]:
        warm.add_request(p, sampling=SamplingParams(
            max_new_tokens=max_new_tokens))
    while warm.has_unfinished():
        warm.step()

    # single-router no-kill baseline: the denominator for vs_baseline
    base_router = FleetRouter(handles)
    t0 = time.perf_counter()
    base_rids = [base_router.add_request(p, sampling=sp(t))
                 for p, t in zip(prompts, tenants)]
    while base_router.has_unfinished():
        base_router.step()
    base_dt = time.perf_counter() - t0
    assert all(base_router.get_request(r).finish_reason == "length"
               for r in base_rids)
    base_rate = base_router.num_dispatched / base_dt

    # replicated pass: N routers, shared store, a mid-run router kill
    store = MemStore()
    fcfg = FleetConfig(heartbeat_interval_s=0.0, router_ttl_s=0.3,
                       lease_ttl_s=0.6, prefix_affinity=False,
                       peer_data_plane=False)
    names = [f"R{i}" for i in range(routers)]
    rts = [FleetRouter(
        handles, fcfg,
        registry=ReplicaRegistry(store, ttl_s=fcfg.registry_ttl_s),
        lease_store=LeaseStore(store, ttl_s=fcfg.lease_ttl_s),
        router_id=name) for name in names]
    for r in rts:
        r.step()  # discover the peer view before any dispatch

    t_add, first_tok, terminals = {}, {}, {}
    t0 = time.perf_counter()
    for i, (p, ten) in enumerate(zip(prompts, tenants)):
        rid = f"b{i}"
        home = next(r for r in rts
                    if r.router_id == tenant_home(ten, sorted(names)))
        home.add_request(rid, p, sampling=sp(ten))
        t_add[rid] = time.perf_counter()
    rounds, victim = 0, None
    try:
        while True:
            now = time.perf_counter()
            assert now - t0 < 300, "replicated pass failed to converge"
            for r in rts:
                for o in r.step():
                    if o.request_id not in first_tok and o.generated:
                        first_tok[o.request_id] = time.perf_counter()
                    if o.finished:
                        assert o.request_id not in terminals, \
                            "duplicate terminal"
                        terminals[o.request_id] = o
            rounds += 1
            if rounds == 3:
                victim = max(rts, key=lambda r: sum(
                    1 for fr in r._open.values()
                    if fr.lease_gen is not None and not fr.finished))
                faults.install(
                    f"fleet.router_kill:flag:{victim.router_id}*1")
            if (len(terminals) == n_requests
                    and rts[0].lease_store.active() == 0):
                break
    finally:
        faults.clear()
    dt = time.perf_counter() - t0

    assert victim is not None and victim.router_dead
    assert sum(r.num_router_failovers for r in rts) == 1
    assert all(o.finish_reason == "length" for o in terminals.values())
    assert all(len(o.generated) == max_new_tokens
               for o in terminals.values())
    ttft_ms = sorted((first_tok[rid] - t_add[rid]) * 1e3
                     for rid in t_add)
    per_router = {r.router_id: {
        "dispatches_per_sec": round(r.num_dispatched / dt, 1),
        "dispatched": r.num_dispatched,
        "adopted": r.lease_store.num_adopted,
        "failovers": r.num_router_failovers,
        "dead": r.router_dead} for r in rts}
    total_rate = sum(r.num_dispatched for r in rts) / dt

    # part 2: the 100-replica spike-trace simulation with autoscale
    sim = FleetSim(n_replicas=100, n_routers=3, max_seqs=4, seed=seed,
                   autoscale=LoadThresholdPolicy(
                       high=0.8, low=0.0, min_replicas=1,
                       max_replicas=110))
    # background trickle plus all-tenant thundering herds: a single
    # tenant's burst only saturates its home router's third of the
    # fleet (fleet-mean load ~0.35, under the 0.8 threshold), so the
    # herd spans every tenant to push the WHOLE fleet past it
    sim_tenants = [f"t{i}" for i in range(8)]
    trace = spike_trace(duration_s=24.0, tenants=sim_tenants,
                        base_rps=10.0, max_new=8, seed=seed)
    for at in (6.0, 14.0):
        for ten in sim_tenants:
            trace.extend(Arrival(t=at, tenant=ten, prompt_len=24,
                                 max_new=8) for _ in range(60))
    trace.sort(key=lambda a: a.t)
    # thundering herds drain in well under a virtual second on the
    # measured latency model, so the autoscaler must tick finer than
    # the default 1.0 s or it never observes the spike load at all
    w0 = time.perf_counter()
    sim.run(trace, autoscale_every_s=0.05)
    sim_wall = time.perf_counter() - w0
    sim_summary = sim.check()
    sim_dispatched = sum(r.num_dispatched for r in sim.routers)

    return {
        "metric": "replicated_router_dispatches_per_sec",
        "value": round(total_rate, 1),
        "unit": "dispatches/sec",
        "vs_baseline": round(total_rate / base_rate, 3),
        "extra": {
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" routers={routers} replicas=4"
                      f" n_req={n_requests} max_new={max_new_tokens}"
                      f" max_num_seqs={max_num_seqs} router_kill@3",
            "single_router_dispatches_per_sec": round(base_rate, 1),
            "routers": per_router,
            "victim": victim.router_id,
            "ttft_ms_p50_under_router_kill": round(
                ttft_ms[len(ttft_ms) // 2], 1),
            "ttft_ms_p99_under_router_kill": round(
                ttft_ms[min(len(ttft_ms) - 1,
                            int(len(ttft_ms) * 0.99))], 1),
            "ttft_ms_max_under_router_kill": round(ttft_ms[-1], 1),
            "sim": {
                **sim_summary,
                "n_replicas_start": 100,
                "wall_s": round(sim_wall, 2),
                "dispatches_per_wall_s": round(
                    sim_dispatched / sim_wall, 1),
                "scale_to_decisions": sim.scale_events[:20],
            },
        },
    }


def bench_tp(tiny=False, tp=2, n_requests=12, max_new_tokens=16,
             max_num_seqs=4, seed=0):
    """TP-sharded serving (``--serving --tp N``): the same unequal-
    length ragged workload through a TP=1 engine and a TP=``tp``
    engine over the forced host-device CPU mesh (the dispatcher
    exports ``xla_force_host_platform_device_count`` before jax
    loads). On CPU the TP number prices GSPMD partition overhead, not
    a speedup — all "devices" share one core pool — so the figure to
    trend is the ratio and the invariants: token parity (greedy AND
    sampled), padded_token_frac == 0 at both degrees, and the
    redistribute counters of a trailing TP=1 → TP=``tp`` KV ship
    (``extra["cross_degree_ship"]``: one reshard, exactly one prompt
    token recomputed — the mandatory uncovered position)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.redistribute import get_stats, reset_stats
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    if len(jax.devices()) < tp:
        raise RuntimeError(
            f"--tp {tp} needs {tp} devices, {len(jax.devices())} "
            f"visible — the dispatcher must set XLA_FLAGS before jax "
            f"imports")
    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    if tiny:
        cfg = LlamaConfig.tiny()
        n_requests, max_new_tokens = min(n_requests, 10), min(
            max_new_tokens, 8)
    else:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(0, cfg.vocab_size,
                                size=6 + 3 * (i % 4)))
               for i in range(n_requests)]
    samplings = [SamplingParams(max_new_tokens=max_new_tokens)
                 if i % 3 else
                 SamplingParams(max_new_tokens=max_new_tokens,
                                temperature=0.8, seed=100 + i)
                 for i in range(n_requests)]

    def serve_degree(degree):
        eng = LLMEngine(model, EngineConfig(
            tp_degree=degree, max_num_seqs=max_num_seqs,
            max_model_len=64))
        # warmup: replay the scenario once so the one ragged step (and
        # its shrinking drain shapes) compiles outside the window
        for i, (p, sp) in enumerate(zip(prompts, samplings)):
            eng.add_request(f"w{i}", list(p), sampling=sp)
        while eng.has_unfinished():
            eng.step()
        warm = {f"w{i}": list(eng.get_request(f"w{i}").generated)
                for i in range(n_requests)}
        eng.reset_metrics()
        t0 = time.perf_counter()
        for i, (p, sp) in enumerate(zip(prompts, samplings)):
            eng.add_request(f"m{i}", list(p), sampling=sp)
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        snap = eng.metrics.snapshot()
        toks = snap["num_generated_tokens"]
        return eng, warm, {
            "tokens_per_sec": round(toks / dt, 2),
            "tpot_ms_avg": snap["tpot_ms_avg"],
            "ttft_ms_avg": snap["ttft_ms_avg"],
            "padded_token_frac": snap["padded_token_frac"],
        }

    e1, toks1, stats1 = serve_degree(1)
    eN, toksN, statsN = serve_degree(tp)
    assert toks1 == toksN, "TP=%d diverged from TP=1" % tp
    assert stats1["padded_token_frac"] == 0.0, stats1
    assert statsN["padded_token_frac"] == 0.0, statsN

    # cross-degree KV ship: 2 decode steps on TP=1, ship into TP=tp
    ship_rng = np.random.RandomState(seed + 9)
    prompt = list(ship_rng.randint(0, cfg.vocab_size, size=24))
    src = LLMEngine(model, EngineConfig(tp_degree=1,
                                        max_num_seqs=max_num_seqs,
                                        max_model_len=64))
    src.add_request("ship", prompt,
                    sampling=SamplingParams(max_new_tokens=6))
    for _ in range(2):
        src.step()
    done = list(src.get_request("ship").generated)
    meta, payload = src.export_kv("ship")
    dst = LLMEngine(model, EngineConfig(tp_degree=tp,
                                        max_num_seqs=max_num_seqs,
                                        max_model_len=64))
    reset_stats()
    dst.import_kv("ship", prompt + done,
                  sampling=SamplingParams(max_new_tokens=6 - len(done)),
                  meta=meta, payload=payload)
    while dst.has_unfinished():
        dst.step()
    rstats = get_stats()
    recomputed = dst.metrics.snapshot()["num_prompt_tokens"]
    assert dst.num_kv_reshards == 1 and recomputed == 1, \
        (dst.num_kv_reshards, recomputed)

    return {
        "metric": "serving_tp_tokens_per_sec",
        "value": statsN["tokens_per_sec"],
        "unit": "tokens/sec",
        # CPU hosts one core pool: the honest baseline is TP=1 on the
        # same mesh, and the ratio prices the partitioning overhead
        "vs_baseline": round(statsN["tokens_per_sec"]
                             / stats1["tokens_per_sec"], 3),
        "extra": {
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "tp_degree": tp,
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" tp={tp} n_req={n_requests}"
                      f" max_new={max_new_tokens}"
                      f" max_num_seqs={max_num_seqs}",
            "tp1": stats1,
            f"tp{tp}": statsN,
            "token_parity": True,
            "cross_degree_ship": {
                "payload_bytes": len(payload),
                "tokens_covered": meta["tokens_covered"],
                "prompt_tokens_recomputed": recomputed,
                "kv_reshards": dst.num_kv_reshards,
                **{k: rstats[k] for k in
                   ("num_redistributes", "bytes_moved", "bytes_total")},
            },
        },
    }


def bench_tiers(tiny=False, n_requests=6, max_new_tokens=12, seed=0):
    """Tiered KV serving (``--serving --tiers``): the same long-context
    workload through an unconstrained big-pool engine and a tiered
    engine whose DEVICE pool is smaller than one request's context (8
    blocks = 32 tokens vs 52-token requests) — demotion instead of
    eviction, promotion instead of recompute. The figure to trend is
    the throughput ratio (the tier tax: host round-trips per token)
    plus the invariants: token parity (greedy AND sampled), a
    counter-asserted zero-recompute park/resume turn, and an
    InProcessReplica fleet offload so every ``serving/kv_tier_*``
    gauge — peer_blocks_used included — is exercised, not just
    emitted."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from paddle_tpu.serving.fleet import (
        FleetConfig, FleetRouter, InProcessReplica,
    )

    paddle.seed(seed)
    paddle.set_default_dtype("float32")
    if tiny:
        cfg = LlamaConfig.tiny()
        n_requests, max_new_tokens = min(n_requests, 4), min(
            max_new_tokens, 8)
    else:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    model.eval()
    base = dict(block_size=4, max_num_seqs=4, max_model_len=96,
                drain_grace_s=0.0)
    rng = np.random.RandomState(seed)
    prompts = [list(map(int, rng.randint(0, cfg.vocab_size, size=40)))
               for _ in range(n_requests)]
    samplings = [SamplingParams(max_new_tokens=max_new_tokens)
                 if i % 2 else
                 SamplingParams(max_new_tokens=max_new_tokens,
                                temperature=0.8, seed=100 + i)
                 for i in range(n_requests)]

    def serve(engine_cfg):
        eng = LLMEngine(model, engine_cfg)
        # warmup replay: the ragged step (and the tiered concat step)
        # compiles outside the measured window
        for i, (p, sp) in enumerate(zip(prompts, samplings)):
            eng.add_request(f"w{i}", list(p), sampling=sp)
        while eng.has_unfinished():
            eng.step()
        eng.reset_metrics()
        t0 = time.perf_counter()
        for i, (p, sp) in enumerate(zip(prompts, samplings)):
            eng.add_request(f"m{i}", list(p), sampling=sp)
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        toks = {f"m{i}": list(eng.get_request(f"m{i}").generated)
                for i in range(n_requests)}
        snap = eng.metrics.snapshot()
        return eng, toks, {
            "tokens_per_sec": round(
                snap["num_generated_tokens"] / dt, 2),
            "tpot_ms_avg": snap["tpot_ms_avg"],
            "ttft_ms_avg": snap["ttft_ms_avg"],
        }

    flat, toks_flat, stats_flat = serve(
        EngineConfig(num_blocks=256, **base))
    tiered, toks_tiered, stats_tiered = serve(
        EngineConfig(num_blocks=8,
                     kv_tiers={"num_host_blocks": 48}, **base))
    assert toks_flat == toks_tiered, \
        "tiered streams diverged from the big-pool reference"

    # park/resume turn on the tiered engine (zero-recompute, counted)
    prompt = prompts[0]
    tiered.add_request("turn1", list(prompt), sampling=samplings[0])
    while tiered.has_unfinished():
        tiered.step()
    turn1 = list(tiered.get_request("turn1").generated)
    tiered.release_request("turn1")
    tiered.park_session("turn1")
    prompt2 = list(prompt) + turn1 + [1, 2, 3]
    hit = tiered.resume_session("turn2", "turn1", prompt2,
                                sampling=samplings[0])
    while tiered.has_unfinished():
        tiered.step()
    assert hit > 0 and \
        tiered._kvtier.num_resume_recomputed_tokens == 0, \
        (hit, tiered._kvtier.num_resume_recomputed_tokens)

    # fleet offload: 2 in-process replicas, a parked session pushed to
    # the cold peer — the source's peer_blocks_used gauge goes live
    reps = [InProcessReplica(
        model, EngineConfig(num_blocks=16, kv_tiers=True, **base),
        replica_id=f"rep{i}") for i in range(2)]
    for r in reps:
        r.start_peer()
    router = FleetRouter(reps, FleetConfig(
        tier_offload_watermark=1e-6))
    rid = router.add_request("sess", list(prompt),
                             sampling=samplings[0])
    while router.has_unfinished():
        router.step()
    router.park_session(rid)
    router.step()   # the offload sweep fires
    assert router.num_session_offloads == 1, \
        router.num_session_offloads
    for r in reps:
        r.close_peer()

    def gauge(name):
        key = f"serving_kv_tier_{name}"
        engines = [tiered] + [r.engine for r in reps]
        return sum(int(e.metrics.snapshot()[key]) for e in engines)

    return {
        "metric": "serving_tiered_tokens_per_sec",
        "value": stats_tiered["tokens_per_sec"],
        "unit": "tokens/sec",
        # the tier tax: same workload, device pool 8 blocks vs 256 —
        # every token pays the demote/promote round-trips
        "vs_baseline": round(stats_tiered["tokens_per_sec"]
                             / stats_flat["tokens_per_sec"], 3),
        "extra": {
            "backend": jax.default_backend(),
            "config": ("tiny" if tiny else "gpt-small-serving")
                      + f" n_req={n_requests}"
                      f" max_new={max_new_tokens}"
                      " device_blocks=8 host_blocks=48",
            "flat": stats_flat,
            "tiered": stats_tiered,
            "token_parity": True,
            "resume_hit_tokens": int(hit),
            "resume_recomputed_tokens": 0,
            # summed over the tiered engine + both fleet replicas
            "kv_tier": {name: gauge(name) for name in
                        ("demotes", "promotes", "host_blocks_used",
                         "peer_blocks_used", "park_resumes")},
            "fleet_ticket_outcomes": dict(router.ticket_outcomes),
        },
    }


def _pp_schedules_worker():
    """Measure per-schedule pipeline step time on the 8-device virtual
    CPU mesh (VERDICT r4 #3/#10: measured numbers, not hardcoded
    constants; relative times are meaningful off-TPU). Prints one JSON
    line: schedule -> {ms_per_step, analytic_bubble}."""
    import jax

    jax.config.update("jax_platforms", "cpu")  # env alone is ignored
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.fleet.pipeline_parallel import (
        LayerDesc, PipelineLayer,
    )
    from paddle_tpu.distributed.fleet.pp_engine import PipelineTrainStep
    from paddle_tpu.distributed.mesh import ProcessMesh

    # compute-dominant size: per-tick layer compute must dwarf the CPU
    # thread-mesh's per-tick sync overhead, or the tick-count difference
    # between schedules is swamped by emulation artifacts (at d<=512 the
    # per-tick sync overhead hides the VPP win)
    D, LAYERS, M, BATCH = 768, 16, 8, 512

    class Block(nn.Layer):
        def __init__(self, d):
            super().__init__()
            self.fc1 = nn.Linear(d, 4 * d)
            self.fc2 = nn.Linear(4 * d, d)
            self.norm = nn.LayerNorm(d)

        def forward(self, x):
            return self.norm(
                x + self.fc2(paddle.ops.gelu(self.fc1(x))))

    rng = np.random.RandomState(0)
    X = paddle.to_tensor(rng.randn(BATCH, D).astype(np.float32))
    Y = paddle.to_tensor(rng.randn(BATCH, D).astype(np.float32))
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "pp"])
    # build + warm ALL engines first, then time them ROUND-ROBIN and
    # report each schedule's MIN — serial per-schedule timing confounds
    # the comparison with host-load drift (observed: two identical
    # programs, gpipe and zero_bubble, differing 50% when timed
    # minutes apart)
    engines = {}
    for schedule, kw in (("1f1b", {}), ("gpipe", {}),
                         ("zero_bubble", {}),
                         ("interleave", {"interleave_degree": 2})):
        paddle.seed(3)
        pipe = PipelineLayer(
            layers=[nn.Linear(D, D)] +
                   [LayerDesc(Block, D) for _ in range(LAYERS)] +
                   [nn.Linear(D, D)],
            num_stages=4, loss_fn=nn.MSELoss())
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=pipe.parameters())
        step = PipelineTrainStep(pipe, nn.MSELoss(), opt, mesh,
                                 n_microbatches=M, schedule=schedule,
                                 **kw)
        float(step(X, Y)._data)  # compile + warm
        engines[schedule] = step
    best = {k: float("inf") for k in engines}
    for _ in range(3):
        for name, step in engines.items():
            t0 = time.perf_counter()
            loss = step(X, Y)
            float(loss._data)
            best[name] = min(best[name], time.perf_counter() - t0)
    # per-rank work accounting: ticks x layers-per-tick. The VPP win is
    # that interleave does FEWER layer-units per rank (smaller ramp);
    # the emulation's per-tick thread-barrier cost (~ms, vs ~us on real
    # ICI) taxes tick-heavy schedules, so the measured table is reported
    # WITH a noise floor self-calibrated from gpipe vs zero_bubble —
    # two byte-identical programs (observed 20%+ apart on this host).
    S, V = 4, 2
    work = {"1f1b": (M // S) * (2 * S - 1) * (LAYERS // S),
            "gpipe": (M + S - 1) * (LAYERS // S),
            "zero_bubble": (M + S - 1) * (LAYERS // S),
            "interleave": (M * V + S - 1) * (LAYERS // (S * V))}
    result = {
        name: {"ms_per_step": round(best[name] * 1000.0, 3),
               "analytic_bubble": round(step.bubble_fraction, 4),
               "layer_units_per_rank": work[name]}
        for name, step in engines.items()
    }
    same = [best["gpipe"], best["zero_bubble"]]
    result["_noise_floor_pct"] = round(
        (max(same) - min(same)) / min(same) * 100.0, 1)
    result["_config"] = (f"S=4 M={M} L={LAYERS} d={D}; V=2 for "
                         f"interleave, V=1 otherwise; 8-dev virtual CPU "
                         f"mesh, round-robin min-of-3 (relative times)")
    result["_note"] = (
        "gpipe and zero_bubble run the SAME compiled program: their "
        "measured delta IS the host noise floor — schedule differences "
        "below it are not resolvable on the CPU-mesh emulation. "
        "interleave (true VPP) executes the fewest layer-units/rank "
        "(smallest ramp, bubble decreasing in V); its per-tick barrier "
        "overhead here is an emulation artifact (~ms/tick on CPU "
        "threads vs ~us over real ICI).")
    print(json.dumps(result))


def _load_prev():
    """Previous round's numbers, for the self-evident regression gate
    (reference bar: tools/ci_op_benchmark.sh CI delta check)."""
    import glob
    import os

    runs = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json")))
    if not runs:
        return {}
    with open(runs[-1]) as f:
        prev = json.load(f)
    extra = prev.get("parsed", prev).get("extra", {})
    out = dict(extra)
    out["_primary"] = prev.get("parsed", prev).get("value")
    return out


def main():
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench.py's default mode measures the chip; the backend here "
            f"is {backend!r}. A CPU run gives no device number.")
    dev = jax.devices()[0]
    tok_1b, mfu, n_params, phases_1b = bench_gpt_1b()
    img_s = bench_resnet50()
    img_s_single, phases_r50 = bench_resnet50_single()
    input_pipe = bench_input_pipeline()
    tok_small, mfu_small = bench_gpt_small()
    prev = _load_prev()

    def ratio(new, old):
        return round(new / old, 3) if old else None

    print(json.dumps({
        "metric": "gpt_1b_bf16_tokens_per_sec_chip",
        "value": round(tok_1b, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / MFU_GATE, 4),
        "extra": {
            "backend": backend,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "gpt_1b_mfu": round(mfu, 4),
            "gpt_1b_params": n_params,
            "gpt_1b_config": "h2048 L16 a16 v32000 seq2048 batch4 bf16 "
                             "flash-attn adamw",
            "gpt_1b_device_phases": phases_1b,
            "resnet50_device_phases": phases_r50,
            # copy_frac as a first-class trend metric across BENCH_r*:
            # r05 measured 0.545 on the 1B GPT — the number the donated
            # train-step buffers + device prefetcher exist to crush
            "copy_frac": {
                "gpt_1b": phases_1b.get("copy_frac"),
                "resnet50": phases_r50.get("copy_frac"),
            },
            "input_pipeline": input_pipe,
            "mfu_gate": MFU_GATE,
            # k=32 steps/dispatch (run_steps) AND the honest single-step
            # number — both reported so no figure hides its methodology
            "resnet50_cifar10_images_per_sec": round(img_s, 1),
            "resnet50_images_per_sec_methodology": "run_steps k=32 "
                "(32 optimizer steps per XLA dispatch, identical "
                "numerics); single-step number below is the per-dispatch "
                "eager-path figure",
            "resnet50_single_step_images_per_sec": round(img_s_single, 1),
            "gpt_small_tokens_per_sec_chip": round(tok_small, 1),
            "gpt_small_mfu": round(mfu_small, 4),
            "vs_prev": {
                "gpt_1b_tokens_per_sec": ratio(tok_1b,
                                               prev.get("_primary")),
                "resnet50_images_per_sec": ratio(
                    img_s, prev.get("resnet50_cifar10_images_per_sec")),
                "gpt_small_tokens_per_sec": ratio(
                    tok_small,
                    prev.get("gpt_small_tokens_per_sec_chip")),
                "methodology_note": "resnet ratio compares k=32 to r4's "
                    "k=32 (same methodology); r3->r4's 4.08x was a "
                    "methodology change, not a chip-utilization win",
            },
        },
    }))


if __name__ == "__main__":
    import sys

    from paddle_tpu.utils.build_cache import enable_compile_cache

    enable_compile_cache()
    if "--pp-schedules-worker" in sys.argv:
        # an 8-virtual-device CPU emulation (~45 min): run it by itself
        # with JAX_PLATFORMS=cpu and
        # XLA_FLAGS=--xla_force_host_platform_device_count=8, never from
        # a process that holds the chip
        _pp_schedules_worker()
    elif "--serving" in sys.argv:
        # serving mode: one BENCH_serving JSON line (tokens/s primary,
        # TTFT/TPOT/occupancy in extra) — tracked across BENCH_r* like
        # copy_frac is. --replicas N routes the same scenario through
        # the fleet router instead (fleet counters in extra); --disagg
        # splits it into prefill/decode roles with KV-block shipping
        # (ship counters + recompute comparison in extra.disagg).
        if "--peer" in sys.argv:
            # peer data plane vs router relay over the same disagg
            # scenario (ship bytes + tokens/s per variant in extra)
            print("BENCH_serving_peer " + json.dumps(
                bench_peer(tiny="--tiny" in sys.argv)))
        elif "--routers" in sys.argv:
            # replicated control plane: N leased routers + a mid-run
            # router kill, plus the 100-replica autoscaled simulation
            n = int(sys.argv[sys.argv.index("--routers") + 1])
            print("BENCH_serving_routers " + json.dumps(
                bench_routers(tiny="--tiny" in sys.argv, routers=n)))
        elif "--tp" in sys.argv:
            # TP-sharded serving: the mesh must exist before jax
            # initialises, so the flag is exported HERE (bench
            # functions import jax lazily)
            n = int(sys.argv[sys.argv.index("--tp") + 1])
            _flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in _flags:
                os.environ["XLA_FLAGS"] = (
                    _flags + " --xla_force_host_platform_device_count"
                    "=%d" % max(4, n)).strip()
            print("BENCH_serving_tp " + json.dumps(
                bench_tp(tiny="--tiny" in sys.argv, tp=n)))
        elif "--tiers" in sys.argv:
            # tiered KV: over-device-pool workload vs the big-pool
            # baseline (throughput ratio = the tier tax) + park/resume
            # and a fleet offload so every kv_tier gauge is exercised
            print("BENCH_serving_tiers " + json.dumps(
                bench_tiers(tiny="--tiny" in sys.argv)))
        elif "--replicas" in sys.argv:
            n = int(sys.argv[sys.argv.index("--replicas") + 1])
            print("BENCH_serving_fleet " + json.dumps(
                bench_fleet(tiny="--tiny" in sys.argv, replicas=n,
                            subprocess_mode="--subprocess"
                                            in sys.argv,
                            disagg="--disagg" in sys.argv)))
        else:
            print("BENCH_serving " + json.dumps(
                bench_serving(tiny="--tiny" in sys.argv)))
    else:
        main()
