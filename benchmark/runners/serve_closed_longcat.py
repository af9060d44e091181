"""Runner ``serve_closed_longcat``: ``serve_closed``'s closed loop against
the LongCat-Flash decoder (``paddle_tpu/models/longcat.py``: double layers
of two latent attentions and two dense FFNs beside a shortcut-connected
expert layer whose softmax router also picks identity experts;
``cache_spec()`` kind ``latent``, two pools a layer; a share of the
routed experts and of the vocabulary), through the same
``FleetRouter([InProcessReplica(model, EngineConfig(**engine))])``.

IMPORTED, not copied: ``ClosedLoop``, ``Record`` and ``wrap_engine_step``
from ``serve_closed`` (the loop, its stamps); ``MoeSpy`` (the spy that
keeps one step whole, over a list of latent pools), ``row_kinds``,
``disputes`` and ``step_compiled`` from ``serve_closed_moe``;
``reduce_samples`` and ``scoped_instructions`` from
``serve_closed_hybrid``; the float8 rounding ``_to_float32`` from
``serve_closed_sparse``. It also tells ``rooflines_dense`` how to count
this block design's dense weights (that module finds a design by the
runner's name).

Traffic (``ZipfStream``): ``traffic.RequestStream``'s sizes, order and
sampling, with each prompt's ids drawn Zipf(``traffic.zipf.s``) over the
vocabulary rows held: rank r with probability proportional to r ** -s,
the rank -> id map a permutation drawn from the traffic file's own
``zipf.zipf_seed`` (the same hot ids in every run); ``--seed`` gives which
ids are drawn, the sampling seeds and the weights.

``correct`` (all outside the window), as ``serve_closed_moe``'s: every
finished request ended ``length`` with exactly its ``max_new_tokens``;
nothing compiled in the window; ``num_logits_fetches == 0``; 0
preemptions; the compiled step's text holds the latent call once an
attention (two a layer) and the grouped product's custom calls
``grouped_calls_per_expert_layer`` times a layer; and after the window the
spy keeps ONE real step whole (a row continuing a chunked prompt, a decode
row past ``min_decode_ctx`` tokens of context, a row started from
nothing), with the cache as it was before the step (the live blocks of
all 8 pools in compact copies). The model's own ``forward_ragged(...,
return_routing=True)`` on that gives logits and each layer's chosen sets;
``benchmark/reference_longcat.py``'s full forward over
``logit_check.rows`` rows' whole histories (float32, ``highest``,
expanded attention, the attention weights upcast a layer at a time, the
dense FFNs' and the experts' a matrix at a time where they are used) runs
with the program's sets FORCED at the step's own rows and says at every
layer which set it would have chosen itself:

(a) **logits**: largest difference over largest logit, rms difference
    over rms logit, under the workload file's limits;
(b) **selection**: wherever the reference's own set differs from the
    program's at a (layer, step row), the reference's margin between the
    disputed candidates' ``p + b`` is under ``selection.epsilon``, and
    such places are at most ``selection.max_share`` of them.

With ``logit_check.probe`` (the rehearsal and the one probe run, never a
cell) the forced reference is computed once more with every weight and
every layer's input rounded to float8_e4m3 in float32 arithmetic: that
reading has to FAIL (a).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import (program, reference_longcat as ref, rooflines_dense,
                       rooflines_longcat, stats, traffic)
from benchmark.runners.serve_closed import (ClosedLoop, Record,
                                            wrap_engine_step)
from benchmark.runners.serve_closed_hybrid import (reduce_samples,
                                                   scoped_instructions)
from benchmark.runners.serve_closed_moe import (MoeSpy, disputes,
                                                step_compiled)
from benchmark.runners.serve_closed_sparse import _to_float32

KERNEL = "ragged_paged_attention"
EXPERT_SCOPE = "moe_experts"
GROUPED = "grouped_matmul"
# keys of the configuration file the model class does not take as they
# stand: the file's own sections, and the two counts that are the chip's
# share there (the class takes the model's own count and the share)
NOT_MODEL_KEYS = ("architectures", "model_type", "torch_dtype", "source",
                  "reduced", "published", "assumed", "deployment", "cache",
                  "block", "n_routed_experts", "vocab_size",
                  "max_position_embeddings", "first_expert", "first_row")
# weights the reference keeps as served and upcasts where it uses them
SERVED = ("experts_", "mlp0_", "mlp1_")
__all__ = ["run", "Record"]

# serve.dense_roofline counts a design's dense weights by the runner's name
rooflines_dense.DESIGNS.setdefault("serve_closed_longcat",
                                   rooflines_longcat.dense_groups)


def build_model(model, positions, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in.
    The file's ``n_routed_experts`` and ``vocab_size`` are what this chip
    HOLDS (from ``first_expert`` / ``first_row`` on); the router keeps the
    published width. The rope table is built as far as the cell's longest
    sequence."""
    import paddle_tpu as paddle
    from paddle_tpu.models.longcat import LongCatConfig, LongCatForCausalLM

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    published = model["published"]
    try:
        return LongCatForCausalLM(LongCatConfig(
            ragged_attn_impl=impl, grouped_matmul_impl=impl,
            max_position_embeddings=min(positions, model[
                "max_position_embeddings"]),
            n_routed_experts=published["n_routed_experts"],
            experts_held=(model["first_expert"], model["n_routed_experts"]),
            vocab_size=published["vocab_size"],
            vocab_held=(model["first_row"], model["vocab_size"]),
            **{k: v for k, v in model.items() if k not in NOT_MODEL_KEYS}))
    finally:
        paddle.set_default_dtype("float32")


class ZipfStream(traffic.RequestStream):
    """``traffic.RequestStream`` with each prompt's ids Zipf-distributed
    over the ``vocab_size`` rows: the same sizes, order and sampling."""

    def __init__(self, spec, vocab_size, seed):
        super().__init__(spec, vocab_size, seed)
        zipf = spec["zipf"]
        weights = np.arange(1, vocab_size + 1, dtype=np.float64) ** (
            -zipf["s"])
        self._cdf = np.cumsum(weights) / weights.sum()
        self._by_rank = np.random.default_rng(zipf["zipf_seed"]).permutation(
            vocab_size)

    def next(self):
        rid, prompt, sampling = super().next()
        ranks = np.searchsorted(self._cdf, self._rng.random(len(prompt)),
                                side="right")
        ranks = np.minimum(ranks, len(self._cdf) - 1)
        return rid, [int(t) for t in self._by_rank[ranks]], sampling


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items, block):
    """The reference's ``run_layer`` for one configuration, compiled once
    an input shape and forcing form."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items),
                                     block=block))


def reference_last_logits(model, cfg, tokens, padded, forced, block,
                          rounded=False):
    """The reference's logits at the last position of ``tokens`` and, per
    layer, its own chosen sets and selection scores at the last
    ``forced["new"]`` positions. The whole history through
    ``reference_longcat.run_layer``, float32, at the one ``padded`` length
    (padding follows the sequence: causal layers never see it, and a
    token's experts see no other token). ``forced``: {"new": n, "sets":
    {layer: (n, K)}}. ``rounded``: every weight and every layer's input
    rounded to float8_e4m3 first."""
    import jax
    import jax.numpy as jnp

    rcfg = {k: cfg[k] for k in ref.KEYS if k not in (
        "n_routed_experts", "first_expert")}
    rcfg.update(n_routed_experts=cfg["published"]["n_routed_experts"],
                first_expert=cfg["first_expert"])
    lower = _to_float32(rounded)
    layer = _layer_program(ref.freeze(rcfg), block)
    t, n = len(tokens), forced["new"]
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens) - cfg["first_row"]
    x = lower(model.embed_tokens.weight._data[jnp.asarray(ids)])
    infos = {}
    for l, lay in enumerate(model.layers):
        p = {}
        for k, v in lay.weights().items():
            if k.startswith(SERVED):
                p[k] = _to_float32(True, v.dtype)(v) if rounded else v
            else:
                p[k] = lower(v)
        given = np.zeros((padded, rcfg["moe_topk"]), np.int32)
        given[t - n:t] = forced["sets"][l]
        mask = np.zeros((padded,), bool)
        mask[t - n:t] = True
        x, info = layer(p, lower(x), routing=(jnp.asarray(given),
                                               jnp.asarray(mask)))
        # one layer's float32 weights at a time: the device allocates
        # the next layer's when they are enqueued, not when they run
        del p
        x.block_until_ready()
        infos[l] = {"own": np.asarray(info["own"][t - n:t]),
                    "sel": np.asarray(info["sel"][t - n:t])}
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    nw = lower(model.final_norm.weight._data)
    lm_head = model.lm_head._data
    cols = lm_head.shape[1]
    step = -(-cols // 4)
    logits = np.concatenate([
        np.asarray(head(x[t - 1:t], lower(lm_head[:, a:a + step]), nw))[0]
        for a in range(0, cols, step)])
    return logits, infos


def compare(model, cfg, kept, limits, selection, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the cache as it was before that step, against the reference over
    each picked row's whole history with the program's sets forced at the
    step's rows: logits (a), and the reference's own choice at each layer
    against the program's (b)."""
    import jax.numpy as jnp

    logits, _, _, _, routing = model.forward_ragged(
        kept["ids"], kept.pop("cache"), {}, kept["bt"], kept["cu"],
        kept["ctx"], kept["nseq"], return_routing=True)
    logits = np.asarray(logits.astype(jnp.float32))
    routing = {l: np.asarray(r) for l, r in enumerate(routing)}
    probe = bool(limits.get("probe"))
    longest = max(r["ctx"] for r in kept["rows"])
    padded = -(-longest // limits["bucket"]) * limits["bucket"]
    worst = {"err": 0.0, "rms": 0.0, "probe_err": 0.0, "probe_rms": 0.0}
    finite, places, disputed, widest = True, 0, 0, 0.0
    for r in kept["rows"]:
        lo, n = int(kept["cu"][r["row"]]), r["new"]
        mine = {l: sets[lo:lo + n] for l, sets in routing.items()}
        forced = {"new": n, "sets": mine}
        got = logits[r["row"]]
        want, infos = reference_last_logits(
            model, cfg, r["tokens"], padded, forced, limits["bucket"])
        peak = float(np.abs(want).max())
        size = float(np.sqrt(np.mean(want ** 2)))
        err = float(np.abs(got - want).max()) / peak
        rms = float(np.sqrt(np.mean((got - want) ** 2))) / size
        finite = finite and bool(np.isfinite(got).all())
        worst["err"], worst["rms"] = (max(worst["err"], err),
                                      max(worst["rms"], rms))
        row_disputed, row_widest = disputes(infos, mine)
        places += n * len(infos)
        disputed += row_disputed
        widest = max(widest, row_widest)
        zero = cfg["published"]["n_routed_experts"]
        facts = dict(logit_check=r["kind"], row=r["row"], new=n,
                     ctx=r["ctx"], max_abs_ref=f"{peak:.4g}",
                     rel_err=f"{err:.4g}", rel_rms=f"{rms:.4g}",
                     places=n * len(infos), disputed=row_disputed,
                     widest_margin=f"{row_widest:.4g}",
                     identity_picks=int(sum((s >= zero).sum()
                                            for s in mine.values())))
        if probe:
            low, low_infos = reference_last_logits(
                model, cfg, r["tokens"], padded, forced, limits["bucket"],
                rounded=True)
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
    model = build_model(model_cfg, wl["engine"]["max_model_len"], ctx.seed,
                        impl=None if impl == "pallas" else impl)
    model.eval()
    params = sum(int(np.prod(p.shape)) for p in model.parameters())
    replica = InProcessReplica(model, EngineConfig(**wl["engine"]),
                               replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        latent_pools=len(engine._cache), parameters=params,
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits, selection = wl["logit_check"], wl["selection"]
    spy_args = dict(min_decode_ctx=limits["min_decode_ctx"],
                    rows=limits["rows"])
    spy = None
    if ctx.trace:
        spy = MoeSpy(engine, spans, keep_sizes=True, **spy_args)
        wrap_engine_step(engine, spans)
    loop = ClosedLoop(router, replica, ZipfStream(
        wl["traffic"], model_cfg["vocab_size"], ctx.seed),
        wl["traffic"]["clients"])

    loop.start()
    while len(loop.finished_once) < loop.clients:
        with spans("router_step"):
            loop.pump()
    warm_steps = loop.step_no

    programs_before = ctx.compiles.programs
    steps_before = engine.metrics.engine_steps
    counted_before = dict(engine.metrics.step_counters)
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
    counted = {k: v - counted_before.get(k, 0)
               for k, v in engine.metrics.step_counters.items()}
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
    # the engine serves nothing after this: its own caches make room for
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
        # calls and what stands under the scope around them
        grouped = program.custom_calls(text, GROUPED)
        experts = sorted(set(grouped) | set(
            scoped_instructions(text, (EXPERT_SCOPE,))))
        say(latent_custom_calls=len(calls), first=calls[:2],
            expert_instructions=len(experts), grouped_calls=len(grouped),
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            layers = model_cfg["num_layers"]
            checks["latent_call_once_per_attention"] = (
                len(calls) == 2 * layers)
            checks["grouped_calls_per_expert_layer"] = len(grouped) == (
                wl["grouped_calls_per_expert_layer"] * layers)
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
        moe_experts_hit=snap["moe_experts_hit"],
        window_zero_rows=counted.get("zero_rows", 0),
        window_expert_assignments=counted.get("expert_assignments", 0))
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
