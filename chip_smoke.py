"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]            one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4             the two cross-chip paths only
    python chip_smoke.py --rehearse [...]      tiny widths on the CPU, no chip

Default run, one chip, GPT "1B" widths (vocab 32000, hidden 2048,
intermediate 5632, 16 layers, 16/16 heads, head dim 128, bf16, seq 2048),
random weights from ``--seed``:

* train phase — ``DataLoader(use_device_prefetch=True)`` ->
  ``paddle.jit.TrainStep`` (AdamW, default donation), batch 4 x seq 2048, a
  few steps on a repeated batch;
* serve phase — the same model behind ``FleetRouter([InProcessReplica])``
  with the default ``EngineConfig`` (``max_model_len=2048``): 8 requests,
  prompts 30-1500 tokens, 32 new tokens, half greedy and half
  seeded-sampled, two arriving mid-flight; a warm-up wave, the served
  wave, and the served wave again from the same seed;
* hybrid phase — ``Phi4FlashForCausalLM`` at its published widths (32
  layers, vocabulary 200,064: state-space, window and full-attention
  layers, a cross-decoder) behind the same router and engine, whose cache
  is then a full pool, a window pool and state slots: the same 8 requests
  served twice from the same seed, streams identical run to run, window
  blocks released, every state slot returned. ``--only hybrid`` runs it
  alone.

``--chips 4`` runs only ``LLMEngine(tp_degree=4)`` against ``tp_degree=1``
and ``ParallelTrainStep`` on a dp2 x tp2 mesh against the one-chip
``TrainStep``. Every check raises; nothing is caught. Without ``--rehearse``
the script exits non-zero at once unless JAX's first device is a TPU. Lines
before the last are set-up facts (compile seconds, step milliseconds, bytes),
each naming the device — not benchmark numbers. The last line of stdout is
the result: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import time

import numpy as np

GPT_1B = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
              num_hidden_layers=16, num_attention_heads=16,
              num_key_value_heads=16, max_position_embeddings=2048)
# --rehearse: control flow only, at widths the Pallas interpreter can walk
TINY = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=128)
BATCH, TRAIN_STEPS = 4, 6
# six requests open the wave, two arrive mid-flight; no length is a
# multiple of the 16-token block (a fully cached prompt would copy-on-write)
PROMPT_LENS, LATE_LENS, NEW_TOKENS = (1500, 700, 1100, 333, 900, 65), (30, 200), 32
GIB = float(1 << 30)


def say(tag, dev, **facts):
    body = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[{tag}] device={dev.device_kind!r} {body}", flush=True)


class Compiles:
    """Counts, from JAX's own monitoring events, every program handed to
    the backend compiler (a persistent-cache hit included) and how many
    of those the persistent cache served."""

    def __init__(self):
        import jax

        self.programs = self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _evt(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def shapes_of(tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


def bytes_in_use(dev):
    from paddle_tpu import device

    return device.memory_stats(dev).get("bytes_in_use")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------
def repeated_batches(cfg_kw, seq, seed, steps):
    """A DataLoader whose every batch is the same BATCH seeded samples."""
    from paddle_tpu.io import DataLoader, Dataset

    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg_kw["vocab_size"],
                       (BATCH, seq + 1)).astype(np.int32)

    class Repeated(Dataset):
        def __len__(self):
            return BATCH * steps

        def __getitem__(self, i):
            row = toks[i % BATCH]
            return row[:-1], row[1:]

    return DataLoader(Repeated(), batch_size=BATCH, shuffle=False,
                      use_device_prefetch=True)


def build_lm(cfg_kw, seed, dtype, flash=True):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    paddle.set_default_dtype(dtype)
    try:
        return LlamaForCausalLM(LlamaConfig(use_flash_attention=flash,
                                            **cfg_kw))
    finally:
        paddle.set_default_dtype("float32")


def run_train_steps(step, loader, compiles):
    """Drive ``step`` over ``loader``; returns (losses, compile seconds,
    step milliseconds, programs compiled after step 1, last batch)."""
    losses, ms, after_first, last = [], [], None, None
    for xb, yb in loader:
        t0 = time.perf_counter()
        losses.append(float(step(xb, yb)._data))  # host fetch: step done
        ms.append((time.perf_counter() - t0) * 1e3)
        if after_first is None:
            after_first = compiles.programs
        last = (xb, yb)
    return (losses, ms[0] / 1e3, ms[1:], compiles.programs - after_first,
            last)


def check_losses(losses, vocab):
    assert all(math.isfinite(v) for v in losses), losses
    assert abs(losses[0] - math.log(vocab)) < 1.0, (
        f"first loss {losses[0]} is not near ln({vocab}) = "
        f"{math.log(vocab):.2f} (random init)")
    assert losses[-1] < losses[0] and min(losses[1:]) < losses[0], (
        f"loss did not fall on a repeated batch: {losses}")


def train_phase(dev, cfg_kw, seed, compiles, on_chip):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import LlamaPretrainingCriterion
    from paddle_tpu.ops.pallas.common import kernel_calls

    seq = cfg_kw["max_position_embeddings"]
    # on the chip the entry point's own rule must pick the kernel; the
    # rehearsal asks for it in interpret mode by name
    model = build_lm(cfg_kw, seed, "bfloat16",
                     flash=True if on_chip else "interpret")
    opt = optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    losses, compile_s, step_ms, recompiled, (xb, yb) = run_train_steps(
        step, repeated_batches(cfg_kw, seq, seed, TRAIN_STEPS), compiles)
    check_losses(losses, cfg_kw["vocab_size"])
    assert len(losses) == TRAIN_STEPS >= 5
    assert recompiled == 0, f"{recompiled} programs compiled after step 1"
    # the kernel is shown from the program that ran, not from a flag
    text = step._jitted.lower(
        1, *shapes_of((step._carry, [p._data for p in step._params],
                       step._slots, [b._data for b in step._buffers],
                       step._lr_arr)),
        step._scaler_state, *shapes_of((xb._data, yb._data))
    ).compile().as_text()     # the same program again: a cache hit
    calls = {k: kernel_calls(text, f"flash_attention_{k}")
             for k in ("fwd", "bwd_dq", "bwd_dkv")}
    if on_chip:
        layers = cfg_kw["num_hidden_layers"]
        assert all(v == layers for v in calls.values()), (
            f"flash tpu_custom_call missing from the compiled train step: "
            f"{calls} (want {layers} of each)")
    stats = paddle.device.memory_stats(dev)
    say("train", dev, batch=BATCH, seq=seq,
        compile_s=round(compile_s, 1),
        step_ms=[round(v, 1) for v in step_ms],
        loss=[round(v, 4) for v in losses], flash_custom_calls=calls,
        recompiled_after_step1=recompiled,
        peak_bytes_in_use=stats["peak_bytes_in_use"] if on_chip else
        "not reported by the cpu backend")


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------
def make_requests(cfg_kw, seed, new_tokens):
    """[(request id, prompt, SamplingParams)]: the last two arrive
    mid-flight; even ones greedy, odd ones seeded-sampled."""
    from paddle_tpu.serving import SamplingParams

    scale = cfg_kw["max_position_embeddings"] / 2048.0
    rng = np.random.RandomState(seed)
    reqs = []
    for i, n in enumerate(PROMPT_LENS + LATE_LENS):
        n = max(2, int(round(n * scale)))
        prompt = [int(t) for t in rng.randint(0, cfg_kw["vocab_size"], n)]
        sp = (SamplingParams(max_new_tokens=new_tokens) if i % 2 == 0 else
              SamplingParams(max_new_tokens=new_tokens, temperature=0.8,
                             top_k=50, top_p=0.95, seed=seed * 1000 + i))
        reqs.append((f"req{i}", prompt, sp))
    return reqs


def serve_wave(front, engine, reqs, new_tokens, replica=None, spy=None):
    """Serve ``reqs`` through ``front`` (a FleetRouter or an LLMEngine),
    the last two admitted once two decode steps have run. Returns the
    token streams by request id; every request must finish "length"."""
    engine.reset_metrics()
    streams = {rid: [] for rid, _, _ in reqs}
    n_late = len(LATE_LENS)
    for rid, prompt, sp in reqs[:-n_late]:
        front.add_request(rid, prompt, sp)
    late = list(reqs[-n_late:])
    while front.has_unfinished() or late:
        if late and engine.metrics.decode_steps >= 2:
            for rid, prompt, sp in late:
                front.add_request(rid, prompt, sp)
            late = []
            if spy is not None:
                spy.want = True     # the next step mixes prefill + decode
        for out in front.step():
            if out.token is not None:
                streams[out.request_id].append(int(out.token))
        if replica is not None and not replica.alive:
            raise RuntimeError("the replica's engine died") \
                from replica.last_error
    for rid, _, _ in reqs:
        req = front.get_request(rid)
        assert req.finish_reason == "length", (rid, req.finish_reason)
        assert len(streams[rid]) == new_tokens, (rid, len(streams[rid]))
        front.release_request(rid)
    return streams


class StepSpy:
    """Stands in for the engine's compiled step for one wave and keeps
    the host inputs of ONE dispatch (ids, block tables, cu_seqlens,
    context_lens, num_seqs) for the ref-vs-kernel comparison."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.want, self.got = False, None
        engine._jstep_ragged = self

    def __call__(self, *args):
        if self.want and self.got is None:
            self.got = tuple(np.array(args[i]) for i in (3, 6, 7, 8, 9))
        return self.real(*args)

    def remove(self):
        self.engine._jstep_ragged = self.real


def compare_attention(dev, model, engine, step_inputs, kernel_impl):
    """Layer 0's ragged attention on one real step's inputs (token ids,
    block tables and lengths as dispatched; q/k/v from the model's own
    projections; the engine's live cache): the kernel at the engine's
    full shape against the jnp reference, which is run 128 tokens at a
    time because it materializes every token's whole context."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import _rope_apply_at
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    ids, bt, cu, ctx, nseq = step_inputs
    t_total, s_slots = ids.shape[0], ctx.shape[0]
    layer = model.llama.layers[0]
    attn = layer.self_attn
    h = layer.input_layernorm(model.llama.embed_tokens(
        paddle.to_tensor(ids.reshape(1, t_total))))
    q = attn.q_proj(h)._data.reshape(1, t_total, attn.n_heads, attn.head_dim)
    k = attn.k_proj(h)._data.reshape(1, t_total, attn.n_kv, attn.head_dim)
    v = attn.v_proj(h)._data.reshape(t_total, attn.n_kv, attn.head_dim)
    seg, pos, valid = rpa._token_layout(t_total, s_slots, jnp.asarray(cu),
                                        jnp.asarray(ctx), jnp.asarray(nseq))
    rope_at = jnp.maximum(pos, 0)
    q, k = _rope_apply_at(q, k, layer.rope_cos._data[rope_at][None],
                          layer.rope_sin._data[rope_at][None])
    q, k = q[0], k[0]
    kc0, vc0 = engine._kcs[0], engine._vcs[0]
    out, kc, vc = jax.jit(
        lambda *a: rpa.ragged_paged_attention(*a, impl=kernel_impl))(
            q, k, v, kc0, vc0, bt, cu, ctx, nseq)
    n_valid = int(cu[int(nseq)])
    assert bool(valid[:n_valid].all()) and not bool(valid[n_valid:].any())
    scale = 1.0 / math.sqrt(attn.head_dim)
    ref_chunk = jax.jit(functools.partial(rpa._ragged_attend_ref,
                                          scale=scale))
    out = np.asarray(out.astype(jnp.float32))
    ref = np.zeros_like(out)
    for lo in range(0, n_valid, 128):   # T is a multiple of 128
        sl = slice(lo, lo + 128)
        ref[sl] = np.asarray(ref_chunk(
            q[sl], kc, vc, jnp.asarray(bt), jnp.asarray(ctx), seg[sl],
            pos[sl], valid[sl]).astype(jnp.float32))
    assert np.isfinite(out).all()
    assert not out[n_valid:].any(), "padding rows of the kernel are not 0"
    err = float(np.abs(out - ref).max())
    peak = float(np.abs(ref).max())
    assert peak > 0 and err <= 3e-2 * peak, (
        f"{kernel_impl} and ref attention disagree beyond bf16 tolerance: "
        f"max |diff| {err} against max |ref| {peak}")
    say("serve", dev, attention_check=f"{kernel_impl} vs ref",
        tokens=n_valid, seqs=int(nseq), max_abs_diff=f"{err:.3g}",
        max_abs_ref=f"{peak:.3g}")


def serve_phase(dev, cfg_kw, seed, compiles, on_chip, new_tokens):
    from paddle_tpu.ops.pallas.common import kernel_calls
    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    model = build_lm(cfg_kw, seed, "bfloat16")
    model.eval()
    t0 = time.perf_counter()
    replica = InProcessReplica(
        model, EngineConfig(max_model_len=cfg_kw["max_position_embeddings"]),
        replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    kernel_impl = "pallas" if on_chip else "interpret"
    say("serve", dev, ragged_attention_impl=kernel_impl,
        token_budget=engine._ragged_T, seq_slots=engine.cfg.max_num_seqs,
        kv_blocks=engine.cfg.num_blocks, donated_cache=engine._donated)
    assert engine._donated == on_chip

    # warm-up wave: other prompts of the same lengths compile the one step
    serve_wave(router, engine, make_requests(cfg_kw, seed + 1, new_tokens),
               new_tokens, replica)
    warm_s = time.perf_counter() - t0
    # served wave, then the same wave again from the same seed
    reqs = make_requests(cfg_kw, seed, new_tokens)
    spy = StepSpy(engine)
    before = compiles.programs
    t0 = time.perf_counter()
    first = serve_wave(router, engine, reqs, new_tokens, replica, spy)
    steps = engine.metrics.engine_steps
    wave_s = time.perf_counter() - t0
    second = serve_wave(router, engine, reqs, new_tokens, replica)
    compiled_in_window = compiles.programs - before
    spy.remove()
    assert compiled_in_window == 0, (
        f"{compiled_in_window} programs compiled inside the served window")
    assert first == second, "a second run from the same seed differs"
    assert engine.num_logits_fetches == 0, engine.num_logits_fetches
    assert spy.got is not None, "no mixed prefill+decode step was seen"

    text = engine_step_text(engine, *spy.got)
    calls = kernel_calls(text, "ragged_paged_attention")
    if on_chip:
        assert calls == cfg_kw["num_hidden_layers"], (
            f"ragged tpu_custom_call missing from the compiled serving "
            f"step: {calls} of {cfg_kw['num_hidden_layers']} layers")
    compare_attention(dev, model, engine, spy.got, kernel_impl)
    say("serve", dev, requests=len(reqs), new_tokens=new_tokens,
        warmup_wave_s=round(warm_s, 1), served_wave_s=round(wave_s, 1),
        served_wave_steps=steps, ragged_custom_calls=calls,
        compiled_in_served_window=compiled_in_window,
        num_logits_fetches=engine.num_logits_fetches,
        second_run_identical=True,
        bytes_in_use=bytes_in_use(dev) if on_chip else
        "not reported by the cpu backend")


def hybrid_phase(dev, seed, on_chip, new_tokens):
    """The model that says what it caches, through the normal path."""
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    t0 = time.perf_counter()
    paddle.seed(seed)
    paddle.set_default_dtype("bfloat16")
    try:
        cfg = (Phi4FlashConfig() if on_chip else Phi4FlashConfig.tiny(
            vocab_size=512, hidden_size=128, sliding_window=16))
        model = Phi4FlashForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    max_len = 2048 if on_chip else 128
    replica = InProcessReplica(model, EngineConfig(max_model_len=max_len),
                               replica_id="h0")
    router = FleetRouter([replica])
    engine = replica.engine
    bm = engine.block_manager
    say("hybrid", dev, layers=cfg.num_hidden_layers, vocab=cfg.vocab_size,
        kv_blocks=engine.cfg.num_blocks,
        window_blocks=engine.cfg.num_window_blocks,
        state_slots=bm.state_slots, donated_cache=engine._donated,
        built_s=round(time.perf_counter() - t0, 1))
    assert engine._cache is not None and not engine.cfg.prefix_cache
    shape = dict(max_position_embeddings=max_len,
                 vocab_size=cfg.vocab_size)
    waves = []
    for _ in range(2):
        t1 = time.perf_counter()
        waves.append(serve_wave(router, engine,
                                make_requests(shape, seed, new_tokens),
                                new_tokens, replica=replica))
        say("hybrid", dev, wave_s=round(time.perf_counter() - t1, 2),
            engine_steps=engine.metrics.engine_steps,
            window_blocks_released=bm.num_window_blocks_released)
        assert bm.state_slots_in_use == 0 and bm.num_used_blocks == 0
        assert bm.num_used_window_blocks == 0
    assert waves[0] == waves[1], "streams differ run to run"
    assert bm.num_window_blocks_released > 0
    assert engine.num_logits_fetches == 0
    say("hybrid", dev, streams_identical=True, requests=len(waves[0]))


def engine_step_text(engine, ids, bt, cu, ctx, nseq):
    """Compiled text of the engine's one ragged step, lowered again from
    the shapes of a real dispatch (a persistent-cache hit)."""
    from jax import ShapeDtypeStruct as sds

    s, r = engine.cfg.max_num_seqs, engine._spec_R
    ids, bt, cu, ctx, nseq = (sds(a.shape, a.dtype)
                              for a in (ids, bt, cu, ctx, nseq))
    sampling = (sds((s, 2), np.uint32), sds((s,), np.float32),     # keys, T
                sds((s,), np.int32), sds((s,), np.float32),   # top-k, top-p
                sds((s, r - 1), np.int32), sds((s,), np.int32))   # drafts
    return engine._jstep_ragged.lower(
        *shapes_of(([p._data for p in engine._params],
                    [b._data for b in engine._buffers], engine._key)),
        ids, *shapes_of((engine._kcs, engine._vcs)), bt, cu, ctx, nseq,
        *sampling).compile().as_text()


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------
def tp_phase(devs, cfg_kw, seed, new_tokens, on_chip):
    """LLMEngine(tp_degree=4) against tp_degree=1 on the same seeded
    requests. float32 weights and full-precision matmuls: TP changes the
    order of the row-parallel reductions, and at bf16 that alone flips
    near-tied tokens of a random-weight model, so stream identity would
    test rounding, not sharding. Depth is cut to 8 layers to hold the
    four-chip call short; widths are as published."""
    import jax

    from paddle_tpu.serving import EngineConfig, LLMEngine

    cfg_kw = dict(cfg_kw, num_hidden_layers=min(
        8, cfg_kw["num_hidden_layers"]))
    reqs = make_requests(cfg_kw, seed, new_tokens)
    streams = {}
    with jax.default_matmul_precision("highest"):
        for tp in (4, 1):
            model = build_lm(cfg_kw, seed, "float32")
            model.eval()
            t0 = time.perf_counter()
            engine = LLMEngine(model, EngineConfig(
                max_model_len=cfg_kw["max_position_embeddings"],
                tp_degree=tp))
            streams[tp] = serve_wave(engine, engine, reqs, new_tokens)
            facts = dict(tp_degree=tp, layers=cfg_kw["num_hidden_layers"],
                         dtype="float32", wave_s=round(
                             time.perf_counter() - t0, 1))
            if tp > 1:
                facts.update(tp_placement(engine, devs[:tp], on_chip))
            say("tp", devs[0], **facts)
            del engine, model
            gc.collect()
    assert streams[4] == streams[1], {
        rid: next((i for i, (a, b) in enumerate(zip(streams[4][rid],
                                                    streams[1][rid]))
                   if a != b), None)
        for rid in streams[1] if streams[4][rid] != streams[1][rid]}
    say("tp", devs[0], streams_identical=True, requests=len(reqs),
        greedy=len(reqs) // 2, sampled=len(reqs) - len(reqs) // 2)


def tp_placement(engine, devs, on_chip):
    """Where the TP engine's cache and weights sit: a quarter of the K
    cache on each device, and — on the chip, from every device's own
    allocator — its shard of the weights plus its share of both caches,
    device 0 holding no more than the others."""
    tp = len(devs)
    assert engine.kv_layout.size == tp
    k_bytes = {d: 0 for d in devs}      # summed over the per-layer arrays
    for layer in engine._kcs:
        shards = layer.addressable_shards
        assert {s.device for s in shards} == set(devs)
        assert all(s.data.nbytes * tp == layer.nbytes for s in shards)
        for s in shards:
            k_bytes[s.device] += s.data.nbytes
    facts = {"k_cache_bytes_per_device": {
        d.id: k_bytes[d] for d in devs}}
    if on_chip:
        placed = {d: 0 for d in devs}
        for arr in ([p._data for p in engine._params]
                    + [*engine._kcs, *engine._vcs]):
            for s in arr.addressable_shards:
                placed[s.device] += s.data.nbytes
        used = {d: bytes_in_use(d) for d in devs}
        facts["bytes_placed_per_device"] = {d.id: placed[d] for d in devs}
        facts["bytes_in_use_per_device"] = {d.id: used[d] for d in devs}
        assert all(used[d] >= 0.95 * placed[d] for d in devs), facts
        assert max(used.values()) <= (
            1.25 * min(used.values()) + 0.125 * GIB), facts
    return facts


def parallel_train_phase(devs, cfg_kw, seed, compiles, on_chip):
    """ParallelTrainStep on a dp2 x tp2 mesh against the one-chip
    TrainStep: 3 steps on the same repeated batch, losses within the
    tolerance __graft_entry__.py uses for its loss alignment."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.engine import ParallelTrainStep
    from paddle_tpu.distributed.mesh import ProcessMesh
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    seq = cfg_kw["max_position_embeddings"]
    flash = True if on_chip else "interpret"
    losses = {}
    for name in ("one_chip", "dp2_tp2"):
        model = build_lm(cfg_kw, seed, "bfloat16", flash=flash)
        opt = optimizer.AdamW(learning_rate=3e-4,
                              parameters=model.parameters())
        crit = LlamaPretrainingCriterion(None)
        if name == "one_chip":
            step = paddle.jit.TrainStep(model, crit, opt)
        else:
            step = ParallelTrainStep(model, crit, opt, ProcessMesh(
                np.arange(4).reshape(2, 2), dim_names=["dp", "mp"]))
        got, compile_s, step_ms, _, _ = run_train_steps(
            step, repeated_batches(cfg_kw, seq, seed, 3), compiles)
        check_losses(got, cfg_kw["vocab_size"])
        losses[name] = got
        say("ptrain", devs[0], step=name, batch=BATCH, seq=seq,
            layers=cfg_kw["num_hidden_layers"],
            compile_s=round(compile_s, 1),
            step_ms=[round(v, 1) for v in step_ms],
            loss=[round(v, 4) for v in got])
        del step, opt, model
        gc.collect()
    np.testing.assert_allclose(losses["one_chip"], losses["dp2_tp2"],
                               rtol=5e-3, atol=1e-4)


# --------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU, kernels interpreted")
    ap.add_argument("--only", choices=("hybrid",), default=None,
                    help="one chip: run this phase alone")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
        # asked for by name: nothing infers interpret mode
        os.environ["PADDLE_RAGGED_ATTN_IMPL"] = "interpret"

    import jax

    devs = jax.devices()
    dev = devs[0]
    on_chip = dev.platform == "tpu"
    if not (on_chip or args.rehearse):
        print(f"chip_smoke.py needs a TPU; JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}). --rehearse runs the "
              f"control flow on the CPU.", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"{len(devs)} visible", file=sys.stderr)
        return 2

    from paddle_tpu.utils.build_cache import enable_compile_cache

    cache = enable_compile_cache()
    compiles = Compiles()
    cfg_kw = TINY if args.rehearse else GPT_1B
    new_tokens = 8 if args.rehearse else NEW_TOKENS
    if args.chips == 4:
        tp_phase(devs, cfg_kw, args.seed, new_tokens, on_chip)
        parallel_train_phase(devs, cfg_kw, args.seed, compiles, on_chip)
    elif args.only == "hybrid":
        hybrid_phase(dev, args.seed, on_chip, new_tokens)
    else:
        train_phase(dev, cfg_kw, args.seed, compiles, on_chip)
        # the phases never share HBM: the trainer's state is gone (and
        # shown gone) before the engine is built, and so is that engine
        # before the next model
        gc.collect()
        if on_chip:
            left = bytes_in_use(dev)
            say("train", dev, bytes_in_use_after_free=left)
            assert left < 0.25 * GIB, left
        serve_phase(dev, cfg_kw, args.seed, compiles, on_chip, new_tokens)
        gc.collect()
        hybrid_phase(dev, args.seed, on_chip, new_tokens)
    say("cache", dev, dir=cache, programs=compiles.programs,
        cache_requests=compiles.requests, cache_hits=compiles.hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
