"""Runner ``serve_closed_olmo_hybrid``: ``serve_closed``'s closed loop
against the Olmo Hybrid decoder (``paddle_tpu/models/olmo_hybrid.py``:
Gated DeltaNet linear attention with its state in the engine's slots
beside QK-normed multi-head attention over the paged pool, in Olmo's
post-norm block), through the same
``FleetRouter([InProcessReplica(model, EngineConfig(**engine))])``.

IMPORTED, not copied: ``ClosedLoop``, ``Record``, ``StepSpy`` and
``wrap_engine_step`` from ``serve_closed``; ``reduce_samples`` and
``step_compiled`` from ``serve_closed_hybrid``; ``row_kinds`` from
``serve_closed_prefix`` (with no shared prefix); the float8 rounding
``_to_float32`` from ``serve_closed_sparse``. It also tells
``rooflines_dense`` how to count this block design's dense weights (that
module finds a design by the runner's name).

Traffic: ``traffic.RequestStream`` (sizes, order and sampling from the
workload file, ids from the whole vocabulary).

``correct`` (all outside the window): every finished request ended
``length`` with exactly its ``max_new_tokens``; nothing compiled in the
window; ``num_logits_fetches == 0``; the ``ragged_paged_attention``
custom call once per full layer and ``ragged_gated_delta`` once per
linear layer in the compiled step; and **logits**: after the window the
spy keeps ONE real step's inputs, with a copy of what the rows to compare
had in the cache before that step (their blocks of the full pools,
renumbered into a compact pool, and their state slots; every other row
is sent to the scratch slot and to no block, which changes nothing of
the compared rows: rows meet in no layer). The rows: the shortest chunk
row that carries state in from an earlier chunk, the shortest decode row
at least ``min_answer`` tokens into its answer, then the deepest decode
rows of at most ``max_ctx`` tokens. The model's own ``forward_ragged``
on them against ``benchmark/reference_olmo_hybrid.py``'s full forward
from position 0 over each row's whole history (float32, ``highest``,
the delta rule token by token, one layer's weights upcast at a time).
Two limits, as shares of the reference's own size: the largest
difference over the largest logit and the rms difference over the rms
logit. With ``logit_check.probe`` the reference is computed once more
with every weight and every layer's input rounded to float8_e4m3: that
reading has to FAIL the limits.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import (program, reference_olmo_hybrid as ref,
                       rooflines_dense, rooflines_olmo_hybrid, stats,
                       traffic)
from benchmark.runners.serve_closed import (ClosedLoop, Record, StepSpy,
                                            wrap_engine_step)
from benchmark.runners.serve_closed_hybrid import (reduce_samples,
                                                   step_compiled)
from benchmark.runners.serve_closed_prefix import row_kinds
from benchmark.runners.serve_closed_sparse import _to_float32

KERNELS = ("ragged_paged_attention", "ragged_gated_delta")
MODEL_KEYS = ref.CFG_KEYS + (
    "vocab_size", "intermediate_size", "num_key_value_heads",
    "max_position_embeddings", "tie_word_embeddings", "attention_bias",
    "linear_num_value_heads", "linear_conv_kernel_dim")
__all__ = ["run", "Record"]

# serve.dense_roofline counts a design's dense weights by the runner's name
rooflines_dense.DESIGNS.setdefault("serve_closed_olmo_hybrid",
                                   rooflines_olmo_hybrid.dense_groups)


def build_model(model, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in."""
    import paddle_tpu as paddle
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    try:
        return OlmoHybridForCausalLM(OlmoHybridConfig(
            ragged_attn_impl=impl, delta_impl=impl,
            **{k: model[k] for k in MODEL_KEYS}))
    finally:
        paddle.set_default_dtype("float32")


class OlmoSpy(StepSpy):
    """``StepSpy`` that, when asked, keeps the first step holding a chunk
    row with carried state and a long-answer decode row: every host
    input, a compact copy of what the rows to compare hold in the cache
    before the step, and their token histories."""

    def __init__(self, engine, spans, keep_sizes, limits):
        super().__init__(engine, spans, keep_sizes)
        self.limits = limits
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
            kinds = row_kinds(args[7], args[8], nseq, 0, answers,
                              self.limits["min_answer"])
            if all(kinds.values()):
                self.kept = self.keep(args, kinds, by_slot, slots)
        return super().__call__(*args)

    def keep(self, args, kinds, by_slot, slots):
        import jax.numpy as jnp

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
        mine = [r["row"] for r in picked]
        # the compared rows' blocks, renumbered 0.. (block 0 stands for
        # every other row's, whose keys no compared row reads), and their
        # slots, renumbered 0..; every other row on the scratch slot
        bt = np.array(args[6])
        blocks = np.unique(bt[mine][bt[mine] >= 0])
        renumber = np.zeros((bt.max() + 2,), np.int32)
        renumber[blocks] = np.arange(1, len(blocks) + 1, dtype=np.int32)
        compact = np.where(bt >= 0, renumber[np.maximum(bt, 0)], -1)
        others = np.ones(len(bt), bool)
        others[mine] = False
        compact[others] = -1
        gather = jnp.asarray(np.concatenate([[0], blocks]).astype(np.int32))
        own = jnp.asarray(slots[mine].astype(np.int32))
        scratch = len(mine)
        new_slots = np.full(slots.shape, scratch, np.int32)
        new_slots[mine] = np.arange(len(mine), dtype=np.int32)
        cache = []
        for layer in args[4]:
            if isinstance(layer, dict):
                cache.append({k: jnp.concatenate(
                    [a[own], jnp.zeros_like(a[-1:])])
                    for k, a in layer.items()})
            else:
                cache.append(tuple(a[gather] for a in layer))
        tables = {k: np.array(v) for k, v in args[5].items()}
        tables["slots"] = new_slots
        return {
            "cache": cache, "tables": tables, "real_tables": {
                k: np.array(v) for k, v in args[5].items()},
            "live_blocks": len(blocks), "ids": np.array(args[3]),
            "bt": compact.astype(np.int32), "real_bt": bt,
            "cu": np.array(args[7]), "ctx": np.array(args[8]),
            "nseq": np.int32(args[9]),
            "sampling": tuple(np.array(a) for a in args[10:]),
            "rows": picked}


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items):
    """The reference's ``run_layer`` for one configuration, compiled once
    a layer kind (its first argument) and input shape."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items)),
                   static_argnums=(0,))


def reference_cfg(cfg):
    """The configuration keys the reference reads, hashable."""
    return {k: tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]
            for k in ref.CFG_KEYS}


def reference_last_logits(model, cfg, tokens, padded, rounded=False):
    """The reference's logits at the last position of ``tokens``: the
    whole history from position 0 through ``reference_olmo_hybrid``'s
    ``run_layer``, float32, one layer's weights upcast at a time, one
    compiled program a layer kind at the one ``padded`` length (padding
    follows the sequence: no causal layer sees it). ``rounded``: every
    weight and every layer's input rounded to float8_e4m3 first (the
    probe)."""
    import jax
    import jax.numpy as jnp

    rcfg = reference_cfg(cfg)
    lower = _to_float32(rounded)
    layer = _layer_program(tuple(rcfg.items()))
    t = len(tokens)
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = lower(model.embed_tokens.weight._data[jnp.asarray(ids)])
    for l, lay in enumerate(model.layers):
        p = {k: lower(v) for k, v in lay.weights().items()}
        x = layer(ref.layer_kind(l, rcfg), p, lower(x))
        # one layer's float32 weights at a time: the device allocates the
        # next layer's when they are enqueued, not when they run
        del p
        x.block_until_ready()
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    nw = lower(model.final_norm_w._data)
    lm_head = model.lm_head._data
    cols = lm_head.shape[1]
    step = -(-cols // 8)
    return np.concatenate([
        np.asarray(head(x[t - 1:t], lower(lm_head[:, a:a + step]), nw))[0]
        for a in range(0, cols, step)])


def compare_logits(model, cfg, kept, limits, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the compact cache, against the reference over each picked row's
    whole history."""
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


def run(ctx):
    import jax

    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    wl, model_cfg, say = ctx.workload, ctx.config, ctx.say
    impl = wl.get("kernel_impl", "pallas")
    model = build_model(model_cfg, ctx.seed,
                        impl=None if impl == "pallas" else impl)
    model.eval()
    replica = InProcessReplica(model, EngineConfig(**wl["engine"]),
                               replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    bm = engine.block_manager
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        block_size=engine.cfg.block_size, state_slots=bm.state_slots,
        parameters=sum(int(np.prod(p.shape)) for p in model.parameters()),
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits = wl["logit_check"]
    spy = None
    if ctx.trace:
        spy = OlmoSpy(engine, spans, True, limits)
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
        jax.devices()[:wl["chips"]]),
        state_bytes_held=snap["serving_state_bytes_held"])

    # after the window: one real step with both kinds of row
    if spy is None:
        spy = OlmoSpy(engine, ctx.no_spans, False, limits)
    spy.want = True
    guard = loop.step_no + limits.get("guard_steps", 1500)
    while spy.kept is None and loop.step_no < guard:
        loop.pump()
    spy.remove()
    kept = spy.kept
    cache_shapes = program.shapes_of(engine._cache)
    # the engine serves nothing after this: its cache makes room for the
    # check's functional updates and the float32 reference
    engine._cache = None
    checks = {"mixed_step_seen": kept is not None}
    calls = {k: [] for k in KERNELS}
    if kept is not None:
        say(kept_step_after=loop.step_no - warm_steps - engine_steps,
            live_blocks=kept["live_blocks"],
            rows=[(r["kind"], r["new"], r["ctx"]) for r in kept["rows"]])
        compiled = step_compiled(engine, spy.real, dict(
            kept, cache=cache_shapes, tables=kept["real_tables"],
            bt=kept["real_bt"]))
        text = compiled.as_text()
        calls = {k: program.custom_calls(text, k) for k in KERNELS}
        say(ragged_custom_calls=len(calls[KERNELS[0]]),
            delta_custom_calls=len(calls[KERNELS[1]]),
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            kinds = rooflines_olmo_hybrid.layer_kinds(model_cfg)
            checks["kernel_once_per_full_layer"] = len(
                calls[KERNELS[0]]) == kinds.count("full_attention")
            checks["delta_kernel_once_per_linear_layer"] = len(
                calls[KERNELS[1]]) == kinds.count("linear_attention")
        checks.update(compare_logits(model, model_cfg, kept, limits, say))

    in_win = [r for r in loop.done if r.times and t0 <= r.times[-1] <= t1]
    checks["all_finished_length"] = all(
        r.reason == "length" and len(r.times) == r.want for r in loop.done)
    checks["no_compile_in_window"] = compiled_in_window == 0
    checks["no_logits_fetch"] = engine.num_logits_fetches == 0
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
        "kernels": calls,
    }
