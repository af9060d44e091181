"""Runner ``serve_closed_moe``: ``serve_closed``'s closed loop against a
latent-attention, routed-expert decoder (``paddle_tpu/models/mla_moe.py``:
``cache_spec()`` kind ``latent``, one pool a layer; a rows-per-expert
histogram rides each step's one fetch), through the same
``FleetRouter([InProcessReplica(model, EngineConfig(**engine))])``.

IMPORTED, not copied: ``ClosedLoop``, ``Record``, ``StepSpy`` and
``wrap_engine_step`` from ``serve_closed`` (the loop, its stamps, the
argument positions 3, 6, 7, 8, 9 of the engine's step); ``reduce_samples``
and ``scoped_instructions`` from ``serve_closed_hybrid``.

``correct`` (all outside the window): every finished request ended
``length`` with exactly its ``max_new_tokens``; no program compiled inside
the window; ``num_logits_fetches == 0``; 0 preemptions; the compiled
step's text holds the ragged kernel's latent call once per layer and the
grouped product's custom calls ``grouped_calls_per_expert_layer`` times
an expert layer (the workload file's count for the route the program
takes); and **logits and selection**: after the window the spy keeps ONE
real step whole (it must hold a row continuing a chunked prompt, a decode
row with more than ``min_decode_ctx`` tokens of context and a row started
from nothing): every host input, and the cache as it was before the step
(the live blocks of the step's rows, copied into a compact pool under
renumbered block tables: the step donates and overwrites the original).
The model's own ``forward_ragged(..., return_routing=True)`` on that
gives logits and each expert layer's chosen sets for the step's rows;
``benchmark/reference_kimivl.py``'s full forward over ``logit_check.rows``
rows' whole token histories (float32, ``highest``, expanded attention, one
layer's weights upcast at a time) runs with the program's chosen sets
FORCED at the step's own rows (the history before the step is routed by
the reference itself: the program routed it in earlier steps, which
nobody kept), and at every expert layer also says which set it would have
chosen itself on that layer's input:

(a) **logits**: the largest logit difference over the largest logit, and
    the rms difference over the rms logit, under the workload file's
    limits;
(b) **selection**: wherever the reference's own set differs from the
    program's at an (expert layer, step row), the reference's margin
    between the disputed experts' ``s + b`` has to be under
    ``selection.epsilon`` (a near-tie that bfloat16 inputs may break either
    way), and the share of such places under ``selection.max_share``.
    The reference chooses freely at each layer, but on hidden states that
    followed the program's sets below it: one flipped near-tie changes a
    token's hidden state for good, and every later layer's choice for it
    with that, which says nothing about the later layers' routers.

With ``logit_check.probe`` (rehearsals and the one probe run, never a
cell) the forced reference is computed once more with every weight and
every layer's input rounded to float8_e4m3, the nearest precision below
the configuration's bfloat16: that reading has to FAIL (a).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import program, reference_kimivl as ref, stats, traffic
from benchmark.runners.serve_closed import (ClosedLoop, Record, StepSpy,
                                            wrap_engine_step)
from benchmark.runners.serve_closed_hybrid import (reduce_samples,
                                                   scoped_instructions)

KERNEL = "ragged_paged_attention"
EXPERT_SCOPE = "moe_experts"
GROUPED = "grouped_matmul"
MODEL_KEYS = ref.KEYS + (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_key_value_heads",
    "n_shared_experts", "n_routed_experts", "scoring_func", "topk_method",
    "n_group", "topk_group", "moe_layer_freq", "q_lora_rank",
    "rope_scaling", "tie_word_embeddings")
__all__ = ["run", "Record"]


def build_model(model, positions, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in;
    the rope table is built as far as the cell's longest sequence."""
    import paddle_tpu as paddle
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    try:
        return MlaMoeForCausalLM(MlaMoeConfig(
            ragged_attn_impl=impl, grouped_matmul_impl=impl,
            max_position_embeddings=min(positions, model[
                "max_position_embeddings"]),
            **{k: model[k] for k in MODEL_KEYS}))
    finally:
        paddle.set_default_dtype("float32")


def row_kinds(cu, ctx, nseq, min_decode_ctx):
    """Live rows of one dispatch by what the check wants of them:
    ``carried`` (a chunk continuing a prompt), ``decode`` (one token on a
    context longer than ``min_decode_ctx``), ``fresh`` (started from
    nothing). {kind: [(row, new tokens, context length)]}, shortest
    first."""
    kinds = {"carried": [], "decode": [], "fresh": []}
    for i in range(int(nseq)):
        n, c = int(cu[i + 1]) - int(cu[i]), int(ctx[i])
        if n <= 0:
            continue
        if c - n == 0:
            kinds["fresh"].append((i, n, c))
        elif n > 1:
            kinds["carried"].append((i, n, c))
        elif c - 1 > min_decode_ctx:
            kinds["decode"].append((i, n, c))
    for rows in kinds.values():
        rows.sort(key=lambda r: r[2])
    return kinds


class MoeSpy(StepSpy):
    """``StepSpy`` that, when asked, keeps the first step holding all
    three kinds of row whole: every host input, the step's live cache
    blocks in a compact copy, the token history of the rows to compare."""

    def __init__(self, engine, spans, keep_sizes, min_decode_ctx, rows):
        super().__init__(engine, spans, keep_sizes)
        self.min_decode_ctx, self.n_rows = min_decode_ctx, rows
        self.kept = None

    def __call__(self, *args):
        if self.want and self.kept is None:
            kinds = row_kinds(args[7], args[8], args[9],
                              self.min_decode_ctx)
            if all(kinds.values()):
                self.kept = self.keep(args, kinds)
        return super().__call__(*args)

    def keep(self, args, kinds):
        import jax.numpy as jnp

        # the shortest row of each kind, then further decode rows
        rows = [kinds[k][0] + (k,) for k in ("carried", "decode", "fresh")]
        rows += [r + ("decode",) for r in kinds["decode"][1:]]
        bt, nseq = np.array(args[6]), int(args[9])
        bm = self.engine.block_manager
        by_first = {bm.block_table(r.request_id)[0]: r
                    for r in self.engine.scheduler.running}
        picked = []
        for i, n, c, kind in rows[:self.n_rows]:
            req = by_first[int(bt[i, 0])]
            picked.append({"row": i, "new": n, "ctx": c, "kind": kind,
                           "tokens": [int(t) for t in req.tokens[:c]]})
        # the cache as it was before the step: the live rows' blocks,
        # renumbered 0.. in a pool rounded up to 1,024 blocks
        live = np.unique(bt[:nseq][bt[:nseq] >= 0])
        pool = -(-len(live) // 1024) * 1024
        ids = np.zeros((pool,), np.int32)
        ids[:len(live)] = live
        renumber = np.full((bt.max() + 2,), -1, np.int32)
        renumber[live] = np.arange(len(live), dtype=np.int32)
        compact = np.where(bt >= 0, renumber[np.maximum(bt, 0)], -1)
        compact[nseq:] = -1
        gather = jnp.asarray(ids)
        return {
            "cache": [layer[gather] for layer in args[4]],
            "live_blocks": len(live), "ids": np.array(args[3]),
            "bt": compact.astype(np.int32), "cu": np.array(args[7]),
            "ctx": np.array(args[8]), "nseq": np.int32(nseq),
            "rows": picked}


def step_compiled(engine, real_step, cache_shapes):
    """The engine's one step, lowered again from the shapes of a real
    dispatch (a persistent-cache hit), for its text and memory."""
    from jax import ShapeDtypeStruct as sds

    s, t = engine.cfg.max_num_seqs, engine._ragged_T
    i32, f32 = np.int32, np.float32
    return real_step.lower(
        *program.shapes_of(([p._data for p in engine._params],
                            [b._data for b in engine._buffers],
                            engine._key)),
        sds((t,), i32), cache_shapes, {},
        sds((s, engine.max_blocks_per_seq), i32), sds((s + 1,), i32),
        sds((s,), i32), sds((), i32), sds((s, 2), np.uint32),
        sds((s,), f32), sds((s,), i32), sds((s,), f32), sds((s, 0), i32),
        sds((s,), i32)).compile()


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items):
    """The reference's ``run_layer`` for one configuration, compiled once
    a layer kind (its first argument), input shape and routing form."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items)),
                   static_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _upcast(round_to):
    """float32 of an array, first rounded to ``round_to`` if given; one
    fused pass, so no second float32 copy stands beside the result."""
    import jax
    import jax.numpy as jnp

    def lower(a):
        a = a.astype(jnp.float32)
        return a if round_to is None else a.astype(round_to).astype(
            jnp.float32)
    return jax.jit(lower)


def reference_last_logits(model, cfg, tokens, padded, forced=None,
                          round_to=None):
    """The reference's logits at the last position of ``tokens`` and, per
    expert layer, its own chosen sets and selection scores at the last
    ``forced["new"]`` positions. The whole history through
    ``reference_kimivl.run_layer``, float32, one layer's weights upcast
    at a time, at the one ``padded`` length (padding follows the
    sequence: causal layers never see it, and a token's experts see no
    other token). ``forced``: {"new": n, "sets": {layer: (n, K)}}: the
    sets forced at the last n positions. ``round_to``: every weight and
    every layer's input rounded to that dtype first."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rcfg = {k: cfg[k] for k in ref.KEYS}

    lower = _upcast(round_to)
    layer = _layer_program(tuple(rcfg.items()))
    t = len(tokens)
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = lower(model.embed_tokens.weight._data[jnp.asarray(ids)])
    infos = {}
    for l, lay in enumerate(model.layers):
        p = {k: lower(v) for k, v in lay.weights().items()}
        routing = None
        if forced is not None and lay.kind == "moe":
            n = forced["new"]
            given = np.zeros((padded, rcfg["num_experts_per_tok"]),
                             np.int32)
            given[t - n:t] = forced["sets"][l]
            mask = np.zeros((padded,), bool)
            mask[t - n:t] = True
            routing = (jnp.asarray(given), jnp.asarray(mask))
        x, info = layer(lay.kind, p, lower(x), routing=routing)
        # one layer's float32 weights at a time: the device allocates
        # the next layer's when they are enqueued, not when they run
        del p
        x.block_until_ready()
        if info and forced is not None:
            n = forced["new"]
            infos[l] = {"own": np.asarray(info["own"][t - n:t]),
                        "sel": np.asarray(info["sel"][t - n:t])}
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    nw = lower(model.final_norm.weight._data)
    lm_head = model.lm_head._data
    cols = lm_head.shape[1]
    step = -(-cols // 8)
    logits = np.concatenate([
        np.asarray(head(x[t - 1:t], lower(lm_head[:, a:a + step]), nw))[0]
        for a in range(0, cols, step)])
    return logits, infos


def disputes(infos, mine):
    """(places the reference's own set differs from the program's,
    widest margin between the disputed experts) over the expert layers
    of one row's step positions."""
    import jax.numpy as jnp

    count, widest = 0, 0.0
    for l, info in infos.items():
        margin = np.asarray(ref.dispute_margin(
            jnp.asarray(info["sel"]), jnp.asarray(info["own"]),
            jnp.asarray(mine[l])))
        differs = np.sort(info["own"], 1) != np.sort(mine[l], 1)
        count += int(differs.any(axis=1).sum())
        widest = max(widest, float(margin.max()))
    return count, widest


def compare(model, cfg, kept, limits, selection, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the cache as it was before that step, against the reference over
    each picked row's whole history with the program's sets forced at
    the step's rows: logits (a), and the reference's own choice at each
    expert layer against the program's (b)."""
    import jax.numpy as jnp

    logits, _, _, routing = model.forward_ragged(
        kept["ids"], kept.pop("cache"), {}, kept["bt"], kept["cu"],
        kept["ctx"], kept["nseq"], return_routing=True)
    logits = np.asarray(logits.astype(jnp.float32))
    routing = {l: np.asarray(r) for l, r in enumerate(routing)
               if r is not None}
    probe = jnp.float8_e4m3fn if limits.get("probe") else None
    longest = max(r["ctx"] for r in kept["rows"])
    padded = -(-longest // limits["bucket"]) * limits["bucket"]
    worst = {"err": 0.0, "rms": 0.0, "probe_err": 0.0, "probe_rms": 0.0}
    finite, places, disputed, widest = True, 0, 0, 0.0
    for r in kept["rows"]:
        lo = int(kept["cu"][r["row"]])
        mine = {l: sets[lo:lo + r["new"]] for l, sets in routing.items()}
        forced = {"new": r["new"], "sets": mine}
        got = logits[r["row"]]
        want, infos = reference_last_logits(model, cfg, r["tokens"],
                                            padded, forced=forced)
        peak = float(np.abs(want).max())
        size = float(np.sqrt(np.mean(want ** 2)))
        err = float(np.abs(got - want).max()) / peak
        rms = float(np.sqrt(np.mean((got - want) ** 2))) / size
        finite = finite and bool(np.isfinite(got).all())
        worst["err"], worst["rms"] = (max(worst["err"], err),
                                      max(worst["rms"], rms))
        # (b): the reference's own choice at the step's rows
        t, n = r["ctx"], r["new"]
        row_disputed, row_widest = disputes(infos, mine)
        places += n * len(infos)
        disputed += row_disputed
        widest = max(widest, row_widest)
        facts = dict(logit_check=r["kind"], row=r["row"], new=n, ctx=t,
                     max_abs_ref=f"{peak:.4g}", rel_err=f"{err:.4g}",
                     rel_rms=f"{rms:.4g}", places=n * len(infos),
                     disputed=row_disputed,
                     widest_margin=f"{row_widest:.4g}")
        if probe is not None:
            low, low_infos = reference_last_logits(
                model, cfg, r["tokens"], padded, forced=forced,
                round_to=probe)
            p_err = float(np.abs(low - want).max()) / peak
            p_rms = float(np.sqrt(np.mean((low - want) ** 2))) / size
            p_disputed, p_widest = disputes(low_infos, mine)
            facts.update(float8_rel_err=f"{p_err:.4g}",
                         float8_rel_rms=f"{p_rms:.4g}",
                         float8_disputed=p_disputed,
                         float8_widest_margin=f"{p_widest:.4g}")
            worst["probe_err"] = max(worst["probe_err"], p_err)
            worst["probe_rms"] = max(worst["probe_rms"], p_rms)
        say(**facts)
    share = disputed / max(places, 1)
    say(logit_limits=f"rel_err<={limits['max_rel_err']} "
        f"rel_rms<={limits['max_rel_rms']}",
        worst_rel_err=f"{worst['err']:.4g}",
        worst_rel_rms=f"{worst['rms']:.4g}",
        selection_limits=f"margin<={selection['epsilon']} "
        f"share<={selection['max_share']}", places=places,
        disputed=disputed, disputed_share=f"{share:.4g}",
        widest_margin=f"{widest:.4g}")
    checks = {"logits_finite": finite,
              "logits_within_limits": (
                  worst["err"] <= limits["max_rel_err"]
                  and worst["rms"] <= limits["max_rel_rms"]),
              "selection_disputes_are_near_ties":
                  widest <= selection["epsilon"],
              "selection_dispute_share_within_limit":
                  share <= selection["max_share"]}
    if probe is not None:
        # the nearest precision below has to come out as not correct,
        # by one of the limits
        checks["float8_reference_fails"] = (
            worst["probe_err"] > limits["max_rel_err"]
            or worst["probe_rms"] > limits["max_rel_rms"])
    return checks


def run(ctx):
    import jax

    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    wl, model_cfg, say = ctx.workload, ctx.config, ctx.say
    impl = wl.get("kernel_impl", "pallas")
    model = build_model(model_cfg, wl["engine"]["max_model_len"], ctx.seed,
                        impl=None if impl == "pallas" else impl)
    model.eval()
    replica = InProcessReplica(model, EngineConfig(**wl["engine"]),
                               replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        latent_lanes=model.config.latent_lanes,
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits, selection = wl["logit_check"], wl["selection"]
    spy_args = dict(min_decode_ctx=limits["min_decode_ctx"],
                    rows=limits["rows"])
    spy = None
    if ctx.trace:
        spy = MoeSpy(engine, spans, keep_sizes=True, **spy_args)
        wrap_engine_step(engine, spans)
    loop = ClosedLoop(router, replica, traffic.RequestStream(
        wl["traffic"], model_cfg["vocab_size"], ctx.seed),
        wl["traffic"]["clients"])

    loop.start()
    while len(loop.finished_once) < loop.clients:
        with spans("router_step"):
            loop.pump()
    warm_steps = loop.step_no

    programs_before = ctx.compiles.programs
    steps_before = engine.metrics.engine_steps
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
    say(memory_peak_bytes_after_window=program.memory_peak_bytes(
        jax.devices()[:wl["chips"]]))

    # after the window: one real step with all three kinds of row, whole
    if spy is None:
        spy = MoeSpy(engine, ctx.no_spans, keep_sizes=False, **spy_args)
    spy.want = True
    guard = loop.step_no + limits.get("guard_steps", 3000)
    while spy.kept is None and loop.step_no < guard:
        loop.pump()
    spy.remove()
    kept = spy.kept
    cache_shapes = program.shapes_of(engine._cache)
    # the engine serves nothing after this: its own cache makes room for
    # the check's functional updates and the float32 reference
    engine._cache = None
    checks = {"mixed_step_seen": kept is not None}
    calls, experts = [], []
    if kept is not None:
        say(kept_step_after=loop.step_no - warm_steps - engine_steps,
            live_blocks=kept["live_blocks"],
            rows=[(r["kind"], r["new"], r["ctx"]) for r in kept["rows"]])
        compiled = step_compiled(engine, spy.real, cache_shapes)
        text = compiled.as_text()
        calls = program.custom_calls(text, KERNEL)
        # the expert layers' device work: the grouped product's custom
        # calls and what stands under the scope around them (the list of
        # work items before, the activation between)
        grouped = program.custom_calls(text, GROUPED)
        experts = sorted(set(grouped) | set(
            scoped_instructions(text, (EXPERT_SCOPE,))))
        say(latent_custom_calls=len(calls), first=calls[:2],
            expert_instructions=len(experts), grouped_calls=len(grouped),
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            layers = model_cfg["num_hidden_layers"]
            checks["latent_call_once_per_layer"] = len(calls) == layers
            checks["grouped_calls_per_expert_layer"] = len(grouped) == (
                wl["grouped_calls_per_expert_layer"]
                * (layers - model_cfg["first_k_dense_replace"]))
        checks.update(compare(model, model_cfg, kept, limits, selection,
                              say))

    in_win = [r for r in loop.done if r.times and t0 <= r.times[-1] <= t1]
    checks["all_finished_length"] = all(
        r.reason == "length" and len(r.times) == r.want for r in loop.done)
    checks["no_compile_in_window"] = compiled_in_window == 0
    checks["no_logits_fetch"] = engine.num_logits_fetches == 0
    checks["no_preemption"] = snap["preemptions"] == 0
    samples, win_steps = reduce_samples(
        loop, spy, spans, setup_s, ctx.seconds, t0, t1,
        (traced_from, traced_to), ctx.trace)
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
        kv_blocks_latent=snap["kv_blocks_latent"],
        moe_expert_rows=snap["moe_expert_rows"],
        moe_experts_hit=snap["moe_experts_hit"])
    return {
        "checks": checks,
        "attempted": len(in_win),
        "failed": sum(1 for r in in_win if r.reason != "length"
                      or len(r.times) != r.want),
        "samples": samples,
        "trace_outer": "router_step",
        "trace_iteration": "engine_step",
        "kernels": {KERNEL: calls, EXPERT_SCOPE: experts},
    }
