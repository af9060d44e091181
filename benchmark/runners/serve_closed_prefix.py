"""Runner ``serve_closed_prefix``: ``serve_closed``'s closed loop against a
model with recurrent state served WITH the prefix trie (Jamba,
``paddle_tpu/models/jamba.py``; ``EngineConfig(prefix_cache=True)``),
through the same ``FleetRouter([InProcessReplica(model,
EngineConfig(**engine))])``.

Traffic (``PrefixStream``): every prompt is the run's ONE shared prefix
(``traffic.shared_prefix`` token ids drawn from the seed, as
``traffic.RequestStream`` draws them) followed by the request's OWN part,
whose lengths ``traffic.own_len`` states; the same sizes in the same order
for every seed. Set-up serves one request (the prefix + 64 tokens) to its
end before the first wave, so the prefix's blocks and the state snapshots
at its chunk ends are in the trie when the clients start; then the usual
warm-up wave.

From ``serve_closed`` it IMPORTS ``ClosedLoop``, ``Record``, ``StepSpy``
and ``wrap_engine_step``; from ``serve_closed_hybrid`` ``reduce_samples``;
from ``serve_closed_sparse`` the float8 rounding of the probe. It also
tells ``rooflines_dense`` how to count this block design's dense weights
(that module finds a design by the runner's name).

``correct`` (all outside the window): every finished request ended
``length`` with exactly its ``max_new_tokens``; nothing compiled in the
window; ``num_logits_fetches == 0``; 0 preemptions; every request after
the set-up one was admitted on a snapshot; the share of admitted prompt
tokens that came from the trie over the window is at least
``min_prefix_hit_share``; the ``ragged_paged_attention`` custom call
stands in the compiled step once per attention layer; and **logits**:
after the window the spy keeps ONE real step's inputs and a copy of the
cache as it was before that step, a step holding a chunk row that
CONTINUES the prompt of a request admitted on the prefix (its slot's state
came from the engine's snapshot -> slot copy and its first chunk), a
decode row at least ``min_answer`` tokens into its answer and the deepest
decode row; the model's own ``forward_ragged`` on them against
``benchmark/reference_jamba.py``'s FULL forward from position 0 over each
row's whole history (prefix + own part + answer; float32, ``highest``, one
layer's weights upcast at a time): a wrong snapshot, restore or cut of the
hit shows as a wrong logit. Two limits, as shares of the reference's own
size: the largest difference over the largest logit and the rms
difference over the rms logit. With ``logit_check.probe`` the reference is
computed once more with every weight and every layer's input rounded to
float8_e4m3: that reading has to FAIL the limits.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import (program, reference_jamba as ref, rooflines_dense,
                       rooflines_jamba, stats, traffic)
from benchmark.runners.serve_closed import (ClosedLoop, Record, StepSpy,
                                            wrap_engine_step)
from benchmark.runners.serve_closed_hybrid import reduce_samples
from benchmark.runners.serve_closed_sparse import _to_float32

KERNEL = "ragged_paged_attention"
MODEL_KEYS = ref.CFG_KEYS + (
    "vocab_size", "intermediate_size", "max_position_embeddings",
    "tie_word_embeddings", "num_experts", "sliding_window",
    "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
    "mamba_conv_bias", "mamba_proj_bias")
__all__ = ["run", "Record"]

# serve.dense_roofline counts a design's dense weights by the runner's name
rooflines_dense.DESIGNS.setdefault("serve_closed_prefix",
                                   rooflines_jamba.dense_groups)


def build_model(model, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in."""
    import paddle_tpu as paddle
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    try:
        return JambaForCausalLM(JambaConfig(
            ragged_attn_impl=impl, **{k: model[k] for k in MODEL_KEYS}))
    finally:
        paddle.set_default_dtype("float32")


class PrefixStream(traffic.RequestStream):
    """``RequestStream`` whose every prompt is the whole shared prefix
    followed by an own part of ``own_len`` tokens: the pool's prompt
    lengths are the own parts', then each grows by the prefix."""

    def __init__(self, spec, vocab_size, seed):
        n = spec["shared_prefix"]
        super().__init__(dict(spec, prompt_len=spec["own_len"],
                              max_total=spec["max_total"] - n),
                         vocab_size, seed)
        self.pool = [(p + n, o) for p, o in self.pool]


def serve_prefix_once(router, stream, say):
    """One request, the shared prefix + 64 own tokens, served to its end:
    afterwards the prefix's blocks and the state at its chunk ends are in
    the trie."""
    from paddle_tpu.serving import SamplingParams

    own = np.random.default_rng([stream.seed, 2]).integers(
        0, stream.vocab, 64)
    router.add_request("warm-prefix",
                       stream.shared + [int(t) for t in own],
                       SamplingParams(max_new_tokens=8))
    steps, done = 0, False
    while not done:
        done = any(o.finished for o in router.step())
        steps += 1
    router.release_request("warm-prefix")
    say(prefix_warmed_in_steps=steps, prefix_tokens=len(stream.shared))


def row_kinds(cu, ctx, nseq, prefix, answers, min_answer):
    """Live rows of one dispatch by what the logit check wants of them:
    ``chunk`` (more than one token, continuing a prompt past the shared
    prefix: its state was loaded from a snapshot and carried through a
    chunk), ``answer`` (a decode row at least ``min_answer`` tokens into
    its answer; ``answers[i]``: tokens generated so far by row i's
    request). {kind: [(row, new tokens, context length)]}, shortest
    first."""
    kinds = {"chunk": [], "answer": []}
    for i in range(int(nseq)):
        n, c = int(cu[i + 1]) - int(cu[i]), int(ctx[i])
        if n > 1 and c - n > prefix:
            kinds["chunk"].append((i, n, c))
        elif n == 1 and answers[i] >= min_answer:
            kinds["answer"].append((i, n, c))
    for rows in kinds.values():
        rows.sort(key=lambda r: r[2])
    return kinds


class PrefixSpy(StepSpy):
    """``StepSpy`` that, when asked, keeps the first step holding both
    kinds of row WHOLE: every host input, a copy of the cache as it was
    before the step (not of the snapshot pool: the rows compared hold
    their state in their slots) and the token history of the rows to
    compare."""

    def __init__(self, engine, spans, keep_sizes, prefix, limits):
        super().__init__(engine, spans, keep_sizes)
        self.prefix, self.limits = prefix, limits
        self.kept = None

    def __call__(self, *args):
        if self.want and self.kept is None:
            slots = np.asarray(args[5]["slots"])
            bm = self.engine.block_manager
            by_slot = {bm.state_slot(r.request_id): r
                       for r in self.engine.scheduler.running}
            nseq = int(args[9])
            answers = [by_slot[int(slots[i])].num_generated
                       for i in range(nseq)]
            kinds = row_kinds(args[7], args[8], nseq, self.prefix, answers,
                              self.limits["min_answer"])
            if all(kinds.values()):
                self.kept = self.keep(args, kinds, by_slot, slots)
        return super().__call__(*args)

    def keep(self, args, kinds, by_slot, slots):
        import jax
        import jax.numpy as jnp

        # the shortest chunk row, the shortest long-answer row, the
        # deepest decode row, then further deep ones
        deep = [r for r in reversed(kinds["answer"])
                if r[2] <= self.limits["max_ctx"]]
        rows = [kinds["chunk"][0] + ("chunk",),
                kinds["answer"][0] + ("answer",)]
        rows += [r + ("deepest",) for r in deep if r != kinds["answer"][0]]
        picked = []
        for i, n, c, kind in rows[:self.limits["rows"]]:
            req = by_slot[int(slots[i])]
            picked.append({"row": i, "new": n, "ctx": c, "kind": kind,
                           "tokens": [int(t) for t in req.tokens[:c]]})
        cache, _snapshots = args[4]
        tables = {k: np.array(v) for k, v in args[5].items()}
        # the model's own forward takes no snapshot pool and no copies
        copies = tables.pop("state_copies")
        return {
            "cache": jax.tree.map(jnp.copy, cache), "tables": tables,
            "restores_in_step": int(copies[0, 0, 0]),
            "ids": np.array(args[3]), "bt": np.array(args[6]),
            "cu": np.array(args[7]), "ctx": np.array(args[8]),
            "nseq": np.int32(args[9]), "rows": picked}


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items):
    """The reference's ``run_layer`` for one configuration, compiled once
    a layer kind (its first argument) and input shape."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items)),
                   static_argnums=(0,))


def reference_last_logits(model, cfg, tokens, padded, rounded=False):
    """The reference's logits at the last position of ``tokens``: the
    whole history from position 0 through ``reference_jamba.run_layer``,
    float32, one layer's weights upcast at a time, one compiled program a
    layer kind at the one ``padded`` length (padding follows the
    sequence: causal layers never see it). ``rounded``: every weight and
    every layer's input rounded to float8_e4m3 first (the probe)."""
    import jax
    import jax.numpy as jnp

    rcfg = {k: cfg[k] for k in ref.CFG_KEYS}
    lower = _to_float32(rounded)
    layer = _layer_program(tuple(rcfg.items()))
    t = len(tokens)
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    embed = model.embed_tokens.weight._data
    x = lower(embed[jnp.asarray(ids)])
    for l, lay in enumerate(model.layers):
        p = {k: lower(v) for k, v in lay.weights().items()}
        x = layer(ref.layer_kind(l, rcfg), p, lower(x))
        # one layer's float32 weights at a time: the device allocates the
        # next layer's when they are enqueued, not when they run
        x.block_until_ready()
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    nw = lower(model.final_norm_w._data)
    rows = embed.shape[0]
    step = -(-rows // 8)
    return np.concatenate([
        np.asarray(head(x[t - 1:t], lower(embed[a:a + step]), nw))[0]
        for a in range(0, rows, step)])


def compare_logits(model, cfg, kept, limits, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the cache as it was before that step, against the reference over
    each picked row's whole history."""
    import jax.numpy as jnp

    logits, _ = model.forward_ragged(
        kept["ids"], kept.pop("cache"), kept["tables"], kept["bt"],
        kept["cu"], kept["ctx"], kept["nseq"])
    logits = np.asarray(logits.astype(jnp.float32))
    probe = bool(limits.get("probe"))
    longest = max(r["ctx"] for r in kept["rows"])
    padded = -(-longest // limits["bucket"]) * limits["bucket"]
    worst = {"err": 0.0, "rms": 0.0, "probe_err": 0.0, "probe_rms": 0.0}
    finite = True
    for r in kept["rows"]:
        got = logits[r["row"]]
        want = reference_last_logits(model, cfg, r["tokens"], padded)
        peak = float(np.abs(want).max())
        size = float(np.sqrt(np.mean(want ** 2)))
        err = float(np.abs(got - want).max()) / peak
        rms = float(np.sqrt(np.mean((got - want) ** 2))) / size
        finite = finite and bool(np.isfinite(got).all())
        facts = dict(logit_check=r["kind"], row=r["row"], new=r["new"],
                     ctx=r["ctx"], max_abs_ref=f"{peak:.4g}",
                     rel_err=f"{err:.4g}", rel_rms=f"{rms:.4g}")
        worst["err"], worst["rms"] = (max(worst["err"], err),
                                      max(worst["rms"], rms))
        if probe:
            low = reference_last_logits(model, cfg, r["tokens"], padded,
                                        rounded=True)
            p_err = float(np.abs(low - want).max()) / peak
            p_rms = float(np.sqrt(np.mean((low - want) ** 2))) / size
            facts.update(float8_rel_err=f"{p_err:.4g}",
                         float8_rel_rms=f"{p_rms:.4g}")
            worst["probe_err"] = max(worst["probe_err"], p_err)
            worst["probe_rms"] = max(worst["probe_rms"], p_rms)
        say(**facts)
    say(logit_limits=f"rel_err<={limits['max_rel_err']} "
        f"rel_rms<={limits['max_rel_rms']}",
        worst_rel_err=f"{worst['err']:.4g}",
        worst_rel_rms=f"{worst['rms']:.4g}")
    checks = {"logits_finite": finite,
              "logits_within_limits": (
                  worst["err"] <= limits["max_rel_err"]
                  and worst["rms"] <= limits["max_rel_rms"])}
    if probe:
        # the nearest precision below has to come out as not correct,
        # by one of the limits
        checks["float8_reference_fails"] = (
            worst["probe_err"] > limits["max_rel_err"]
            or worst["probe_rms"] > limits["max_rel_rms"])
    return checks


def step_compiled(engine, real_step, kept, cache_shapes):
    """The engine's one step, lowered again from the shapes of the kept
    dispatch (a persistent-cache hit), for its text and memory."""
    from jax import ShapeDtypeStruct as sds

    def host(a):
        return sds(a.shape, a.dtype)

    s, r = engine.cfg.max_num_seqs, engine._spec_R
    sampling = (sds((s, 2), np.uint32), sds((s,), np.float32),
                sds((s,), np.int32), sds((s,), np.float32),
                sds((s, r - 1), np.int32), sds((s,), np.int32))
    tables = {k: host(v) for k, v in kept["tables"].items()}
    tables["state_copies"] = sds((2, s + 1, 2), np.int32)
    return real_step.lower(
        *program.shapes_of(([p._data for p in engine._params],
                            [b._data for b in engine._buffers],
                            engine._key)),
        host(kept["ids"]), cache_shapes, tables, host(kept["bt"]),
        host(kept["cu"]), host(kept["ctx"]), host(kept["nseq"]),
        *sampling).compile()


def run(ctx):
    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    wl, model_cfg, say = ctx.workload, ctx.config, ctx.say
    impl = wl.get("kernel_impl", "pallas")
    model = build_model(model_cfg, ctx.seed,
                        impl=None if impl == "pallas" else impl)
    model.eval()
    say(parameters=sum(int(np.prod(p.shape)) for p in model.parameters()))
    replica = InProcessReplica(model, EngineConfig(**wl["engine"]),
                               replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    bm = engine.block_manager
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        block_size=engine.cfg.block_size, state_slots=bm.state_slots,
        state_snapshots=bm.state_snapshots, donated_cache=engine._donated,
        built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits = wl["logit_check"]
    prefix = wl["traffic"]["shared_prefix"]
    spy = None
    if ctx.trace:
        spy = PrefixSpy(engine, spans, True, prefix, limits)
        wrap_engine_step(engine, spans)
    stream = PrefixStream(wl["traffic"], model_cfg["vocab_size"], ctx.seed)
    loop = ClosedLoop(router, replica, stream, wl["traffic"]["clients"])

    serve_prefix_once(router, stream, say)
    loop.start()
    while len(loop.finished_once) < loop.clients:
        with spans("router_step"):
            loop.pump()
    warm_steps = loop.step_no

    programs_before = ctx.compiles.programs
    steps_before = engine.metrics.engine_steps
    hit0, cut0, admitted0 = (bm.num_prefix_hit_tokens,
                             bm.num_prefix_recomputed_tokens,
                             engine.scheduler.num_admitted_prompt_tokens)
    setup_s = ctx.since_start()
    t0 = time.perf_counter()
    t1 = t0 + ctx.seconds
    trace_from = t1 - min(ctx.trace_seconds, ctx.seconds / 2)
    traced_from = None
    while time.perf_counter() < t1:
        if ctx.trace and traced_from is None and \
                time.perf_counter() >= trace_from:
            ctx.start_trace()
            traced_from = time.perf_counter()
        with spans("router_step"):
            loop.pump()
    traced_to = time.perf_counter()
    if traced_from is not None:
        ctx.stop_trace()
    compiled_in_window = ctx.compiles.programs - programs_before
    engine_steps = engine.metrics.engine_steps - steps_before
    snap = engine.metrics.snapshot()
    admitted = engine.scheduler.num_admitted_prompt_tokens - admitted0
    hit_share = (bm.num_prefix_hit_tokens - hit0) / max(admitted, 1)
    say(prefix_hit_share=round(hit_share, 4), admitted_prompt_tokens=admitted,
        prefix_recomputed_tokens=bm.num_prefix_recomputed_tokens - cut0,
        state_snapshots_in_use=snap["state_snapshots_in_use"],
        state_snapshot_hits=snap["state_snapshot_hits"],
        state_snapshot_evictions=snap["state_snapshot_evictions"])

    # after the window: one real step with both kinds of row, whole
    if spy is None:
        spy = PrefixSpy(engine, ctx.no_spans, False, prefix, limits)
    spy.want = True
    guard = loop.step_no + limits.get("guard_steps", 1500)
    while spy.kept is None and loop.step_no < guard:
        loop.pump()
    spy.remove()
    kept = spy.kept
    # every request the engine admitted after the set-up one (finished
    # or running now; the router or the queue may hold newer ones)
    every_restored = bm.num_snapshot_hits == (
        len(loop.done) + engine.scheduler.num_running)
    cache_shapes = program.shapes_of((engine._cache, engine._snaps))
    # the engine serves nothing after this: its cache and its snapshots
    # make room for the copy's functional updates and the float32 reference
    engine._cache = engine._snaps = None
    checks = {"mixed_step_seen": kept is not None}
    calls = []
    if kept is not None:
        say(kept_step_after=loop.step_no - warm_steps - engine_steps,
            restores_in_step=kept["restores_in_step"],
            rows=[(r["kind"], r["new"], r["ctx"]) for r in kept["rows"]])
        compiled = step_compiled(engine, spy.real, kept, cache_shapes)
        text = compiled.as_text()
        calls = program.custom_calls(text, KERNEL)
        say(ragged_custom_calls=len(calls), first=calls[:2],
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            checks["kernel_once_per_attention_layer"] = len(calls) == \
                rooflines_jamba.layer_kinds(model_cfg).count("attention")
        checks.update(compare_logits(model, model_cfg, kept, limits, say))

    in_win = [r for r in loop.done if r.times and t0 <= r.times[-1] <= t1]
    checks["all_finished_length"] = all(
        r.reason == "length" and len(r.times) == r.want for r in loop.done)
    checks["no_compile_in_window"] = compiled_in_window == 0
    checks["no_logits_fetch"] = engine.num_logits_fetches == 0
    checks["no_preemptions"] = snap["preemptions"] == 0
    checks["every_request_restored"] = every_restored
    checks["prefix_hit_share"] = hit_share >= wl["min_prefix_hit_share"]
    samples, win_steps = reduce_samples(
        loop, spy, spans, setup_s, ctx.seconds, t0, t1,
        (traced_from, traced_to), ctx.trace)
    # the time to a first token is a fact here, not one of the cell's
    # metrics: its p90 waits ~18 steps for the one chunk a step and moves
    # 8-9 % between seeds with the host's rare slow steps (PERF.md)
    say(ttft_ms_p50=round(stats.percentile(samples["ttft_ms"], 50), 1),
        ttft_ms_p90=round(stats.percentile(samples["ttft_ms"], 90), 1),
        ttft_steps_p90=stats.percentile(samples["ttft_steps"], 90))
    walls = sorted((s[1] - s[0]) * 1e3 for s in win_steps)
    say(median_step_ms=round(stats.percentile(walls, 50), 2),
        slowest_steps_ms=[round(v, 1) for v in walls[-3:]])
    say(warmup_steps=warm_steps, window_steps=len(win_steps),
        engine_steps=engine_steps, requests_finished=len(in_win),
        first_tokens=len(samples["ttft_ms"]), gaps=len(samples["itl_ms"]),
        out_tokens=samples["out_tokens"],
        compiled_in_window=compiled_in_window,
        mixed_steps=engine.metrics.mixed_steps,
        decode_steps=engine.metrics.decode_steps,
        prefill_steps=engine.metrics.prefill_steps,
        preemptions=snap["preemptions"],
        kv_blocks_full=snap["kv_blocks_full"],
        state_slots_in_use=snap["state_slots_in_use"])
    return {
        "checks": checks,
        "attempted": len(in_win),
        "failed": sum(1 for r in in_win if r.reason != "length"
                      or len(r.times) != r.want),
        "samples": samples,
        "trace_outer": "router_step",
        "trace_iteration": "engine_step",
        "kernels": {KERNEL: calls},
    }
