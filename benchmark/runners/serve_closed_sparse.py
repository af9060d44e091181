"""Runner ``serve_closed_sparse``: ``serve_closed``'s closed loop against
the dots3-note-prev decoder (``paddle_tpu/models/dots3.py``: full layers
with a learned sparse indexer over a latent pool and an index-key pool,
``cache_spec()`` kind ``latent_indexed``; sliding layers of latent entries
in the window pool, ``latent_window``; a share of the routed experts),
through the same ``FleetRouter([InProcessReplica(model,
EngineConfig(**engine))])``.

IMPORTED, not copied: ``ClosedLoop``, ``Record``, ``StepSpy`` and
``wrap_engine_step`` from ``serve_closed`` (the loop, its stamps, the
argument positions 3..9 of the engine's step); ``reduce_samples`` and
``scoped_instructions`` from ``serve_closed_hybrid``; ``row_kinds`` and
``disputes`` from ``serve_closed_moe``.

``correct`` (all outside the window), as ``serve_closed_moe``'s with one
check more: every finished request ended ``length`` with exactly its
``max_new_tokens``; no program compiled inside the window;
``num_logits_fetches == 0``; 0 preemptions; the compiled step's text
holds the sparse latent call and the index kernel once a full layer, the
window latent call once a sliding layer and the grouped product's custom
calls ``grouped_calls_per_expert_layer`` times an expert layer; and after
the window the spy keeps ONE real step whole (it must hold a row
continuing a chunked prompt past ``logit_check.min_carried_ctx`` tokens, a
decode row with more than ``min_decode_ctx`` tokens of context and a row
started from nothing): every host input, and the caches as they were
before the step (the live blocks of the step's rows of BOTH pools, copied
into compact pools under renumbered tables). The model's own
``forward_ragged(..., return_routing=True)`` on that gives logits, each
expert layer's chosen sets and each full layer's index selection for the
step's rows; ``benchmark/reference_dots3.py``'s full forward over
``logit_check.rows`` rows' whole token histories (float32, ``highest``,
expanded attention, a dense index score matrix, one layer's weights upcast
at a time and an expert at a time) runs with the program's expert sets and
index sets FORCED at the step's own rows, and says at every layer which
sets it would have chosen itself on that layer's input:

(a) **logits**: largest difference over largest logit, rms difference
    over rms logit, under the workload file's limits;
(b) **expert selection**: as ``serve_closed_moe``'s (disputed places'
    margin and share);
(c) **index selection**: wherever the reference's own ``S_t`` differs
    from the program's at a (full layer, step row), the disputed
    positions' index scores lie within ``index_selection.epsilon`` row
    spreads of the smallest selected score, and the disputed positions are
    at most ``index_selection.max_share`` of the selected ones. A program
    that selects from the future reads an infinite gap.

With ``logit_check.probe`` the forced reference is computed once more with
every weight and every layer's input rounded to float8_e4m3: that reading
has to FAIL (a). The rounding is ``_to_float32`` below, in float32
arithmetic: inside a ``jax.jit`` the TPU's compiler drops a ``convert`` to
float8 that a ``convert`` back follows (it may keep excess precision), so
``astype(float8_e4m3fn).astype(float32)`` there rounds nothing (my chip
run, PR 35).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import program, reference_dots3 as ref, stats, traffic
from benchmark.runners.serve_closed import (ClosedLoop, Record, StepSpy,
                                            wrap_engine_step)
from benchmark.runners.serve_closed_hybrid import (reduce_samples,
                                                   scoped_instructions)
from benchmark.runners.serve_closed_moe import disputes, row_kinds

SPARSE, WINDOW, INDEX = ("ragged_sparse_latent_attention",
                         "ragged_window_latent_attention",
                         "ragged_index_scores")
INDEX_SCOPES = ("index_scores", "index_select")
EXPERT_SCOPE = "moe_experts"
GROUPED = "grouped_matmul"
# keys of the configuration file the model class does not take as they
# stand: the file's own sections, and the two counts that are the chip's
# share there (the class takes the model's own count and the share)
NOT_MODEL_KEYS = ("architectures", "model_type", "torch_dtype", "source",
                  "reduced", "published", "assumed", "deployment", "cache",
                  "block", "n_routed_experts", "vocab_size",
                  "max_position_embeddings", "first_expert", "first_row")
__all__ = ["run", "Record"]


def build_model(model, positions, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in.
    The file's ``n_routed_experts`` and ``vocab_size`` are what this chip
    HOLDS (from ``first_expert`` / ``first_row`` on); the router keeps the
    published width. The rope tables are built as far as the cell's
    longest sequence."""
    import paddle_tpu as paddle
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    published = model["published"]
    try:
        return Dots3ForCausalLM(Dots3Config(
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


def compact(table, nseq):
    """(live block ids padded to a pool of whole 1,024s, the table
    renumbered into that pool) of the first ``nseq`` rows of a block
    table; entries that are -1 stay -1."""
    live = np.unique(table[:nseq][table[:nseq] >= 0])
    pool = max(-(-len(live) // 1024), 1) * 1024
    ids = np.zeros((pool,), np.int32)
    ids[:len(live)] = live
    renumber = np.full((int(table.max()) + 2,), -1, np.int32)
    renumber[live] = np.arange(len(live), dtype=np.int32)
    out = np.where(table >= 0, renumber[np.maximum(table, 0)], -1)
    out[nseq:] = -1
    return ids, out.astype(np.int32), len(live)


class SparseSpy(StepSpy):
    """``StepSpy`` that, when asked, keeps the first step holding all
    three kinds of row whole: every host input, the step's live blocks of
    the main pool (latent entries and index keys) and of the window pool
    in compact copies, the token history of the rows to compare."""

    def __init__(self, engine, spans, keep_sizes, min_decode_ctx,
                 min_carried_ctx, rows):
        super().__init__(engine, spans, keep_sizes)
        self.min_decode_ctx, self.min_carried_ctx = (min_decode_ctx,
                                                     min_carried_ctx)
        self.n_rows, self.kept = rows, None

    def __call__(self, *args):
        if self.want and self.kept is None:
            kinds = row_kinds(args[7], args[8], args[9],
                              self.min_decode_ctx)
            # a chunk whose rows all select: its first row already sees
            # more keys than the indexer keeps
            kinds["carried"] = [r for r in kinds["carried"]
                                if r[2] - r[1] >= self.min_carried_ctx]
            if all(kinds.values()):
                self.kept = self.keep(args, kinds)
        return super().__call__(*args)

    def keep(self, args, kinds):
        import jax.numpy as jnp

        # the shortest row of each kind, then further decode rows
        rows = [kinds[k][0] + (k,) for k in ("carried", "decode", "fresh")]
        rows += [r + ("decode",) for r in kinds["decode"][1:]]
        bt, wbt, nseq = (np.array(args[6]), np.array(args[5]["window"]),
                         int(args[9]))
        bm = self.engine.block_manager
        by_first = {bm.block_table(r.request_id)[0]: r
                    for r in self.engine.scheduler.running}
        picked = []
        for i, n, c, kind in rows[:self.n_rows]:
            req = by_first[int(bt[i, 0])]
            picked.append({"row": i, "new": n, "ctx": c, "kind": kind,
                           "tokens": [int(t) for t in req.tokens[:c]]})
        main_ids, main_table, main_live = compact(bt, nseq)
        win_ids, win_table, win_live = compact(wbt, nseq)
        main_ids, win_ids = jnp.asarray(main_ids), jnp.asarray(win_ids)
        cache = [tuple(a[main_ids] for a in layer)
                 if isinstance(layer, tuple) else layer[win_ids]
                 for layer in args[4]]
        return {"cache": cache, "live_blocks": main_live,
                "live_window_blocks": win_live, "ids": np.array(args[3]),
                "bt": main_table, "wbt": win_table,
                "cu": np.array(args[7]), "ctx": np.array(args[8]),
                "nseq": np.int32(nseq), "rows": picked}


def step_compiled(engine, real_step, cache_shapes):
    """The engine's one step, lowered again from the shapes of a real
    dispatch (a persistent-cache hit), for its text and memory."""
    from jax import ShapeDtypeStruct as sds

    s, t = engine.cfg.max_num_seqs, engine._ragged_T
    i32, f32 = np.int32, np.float32
    table = sds((s, engine.max_blocks_per_seq), i32)
    return real_step.lower(
        *program.shapes_of(([p._data for p in engine._params],
                            [b._data for b in engine._buffers],
                            engine._key)),
        sds((t,), i32), cache_shapes, {"window": table}, table,
        sds((s + 1,), i32), sds((s,), i32), sds((), i32),
        sds((s, 2), np.uint32), sds((s,), f32), sds((s,), i32),
        sds((s,), f32), sds((s, 0), i32), sds((s,), i32)).compile()


@functools.lru_cache(maxsize=None)
def _to_float32(rounded, dtype=None):
    """float32 of an array (or ``dtype``, if given), first rounded to the
    nearest float8_e4m3fn value if ``rounded``: 3 mantissa bits, exponents
    from -6 (below that the spacing stays 2 ** -9), magnitudes up to 448,
    ties to even. One fused pass; bit for bit what ``ml_dtypes`` converts
    to on the CPU (``benchmark/tests/test_benchmark_sparse.py``)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def lower(a):
        x = a.astype(f32)
        if rounded:
            m = jnp.minimum(jnp.abs(x), 448.0)
            _, ex = jnp.frexp(m)          # m = mantissa * 2 ** ex, in [.5, 1)
            q = jnp.ldexp(f32(1), jnp.maximum(ex - 1, -6) - 3)
            x = jnp.sign(x) * jnp.round(m / q) * q
        return x if dtype is None else x.astype(dtype)
    return jax.jit(lower)


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items, block):
    """The reference's ``run_layer`` for one configuration, compiled once
    a layer kind (its first argument), input shape and forcing form."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items),
                                     block=block), static_argnums=(0,))


def reference_last_logits(model, cfg, tokens, padded, forced, block,
                          rounded=False):
    """The reference's logits at the last position of ``tokens`` and, per
    layer, its own choices at the last ``forced["new"]`` positions: the
    expert sets and selection scores of an expert layer, the index scores
    and index set of a full layer. The whole history through
    ``reference_dots3.run_layer``, float32, one layer's weights upcast at
    a time (the experts' stay as served and are upcast one at a time
    inside), at the one ``padded`` length (padding follows the sequence:
    no layer's real row sees it). ``forced``: {"new": n, "sets": {layer:
    (n, K)}, "index": {layer: (n, padded) bool}}. ``rounded``: every
    weight and every layer's input rounded to float8_e4m3 first."""
    import jax
    import jax.numpy as jnp

    rcfg = {k: cfg[k] for k in ref.KEYS}
    lower = _to_float32(rounded)
    layer = _layer_program(ref.freeze(rcfg), block)
    t, n = len(tokens), forced["new"]
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    x = lower(model.embed_tokens.weight._data[jnp.asarray(ids)])
    infos = {}
    for l, lay in enumerate(model.layers):
        p = {}
        for k, v in lay.weights().items():
            if k.startswith("experts_"):
                p[k] = _to_float32(True, v.dtype)(v) if rounded else v
            else:
                p[k] = lower(v)
        routing = index = None
        if lay.ffn == "moe":
            given = np.zeros((padded, rcfg["num_experts_per_tok"]), np.int32)
            given[t - n:t] = forced["sets"][l]
            mask = np.zeros((padded,), bool)
            mask[t - n:t] = True
            routing = (jnp.asarray(given), jnp.asarray(mask))
        if lay.attn == "full":
            index = (jnp.asarray(forced["index"][l]), jnp.int32(t - n))
        x, info = layer(ref.layer_kind(l, rcfg), p, lower(x),
                        routing=routing, index=index)
        # one layer's float32 weights at a time: the device allocates
        # the next layer's when they are enqueued, not when they run
        del p
        x.block_until_ready()
        infos[l] = {k: np.asarray(v[t - n:t] if k in ("own", "sel") else v)
                    for k, v in info.items()
                    if k in ("own", "sel", "idx_scores", "idx_own")}
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    nw = lower(model.final_norm.weight._data)
    lm_head = model.lm_head._data
    cols = lm_head.shape[1]
    step = -(-cols // 4)
    logits = np.concatenate([
        np.asarray(head(x[t - 1:t], lower(lm_head[:, a:a + step]), nw))[0]
        for a in range(0, cols, step)])
    return logits, infos


def index_disputes(infos, mine):
    """(positions selected by the program, positions in exactly one of
    the two sets, widest gap in row spreads) over the full layers of one
    row's step positions."""
    import jax.numpy as jnp

    selected = differ = 0
    widest = 0.0
    for l, sel in mine.items():
        n_differ, gap = ref.index_dispute(
            jnp.asarray(infos[l]["idx_scores"]),
            jnp.asarray(infos[l]["idx_own"]), jnp.asarray(sel))
        selected += int(sel.sum())
        differ += int(np.asarray(n_differ).sum())
        widest = max(widest, float(np.asarray(gap).max()))
    return selected, differ, widest


def compare(model, cfg, kept, limits, selection, index_selection, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the caches as they were before that step, against the reference over
    each picked row's whole history with the program's sets forced at the
    step's rows: logits (a), expert selection (b), index selection (c)."""
    import jax.numpy as jnp

    logits, _, _, _, routing, selections = model.forward_ragged(
        kept["ids"], kept.pop("cache"), {"window": kept["wbt"]},
        kept["bt"], kept["cu"], kept["ctx"], kept["nseq"],
        return_routing=True)
    logits = np.asarray(logits.astype(jnp.float32))
    routing = {l: np.asarray(r) for l, r in enumerate(routing)
               if r is not None}
    selections = {l: np.asarray(s) != 0 for l, s in enumerate(selections)
                  if s is not None}
    probe = bool(limits.get("probe"))
    longest = max(r["ctx"] for r in kept["rows"])
    padded = -(-longest // limits["bucket"]) * limits["bucket"]
    worst = {"err": 0.0, "rms": 0.0, "probe_err": 0.0, "probe_rms": 0.0}
    finite, places, disputed, widest = True, 0, 0, 0.0
    idx = {"selected": 0, "differ": 0, "widest": 0.0}
    for r in kept["rows"]:
        lo, n = int(kept["cu"][r["row"]]), r["new"]
        mine = {l: sets[lo:lo + n] for l, sets in routing.items()}
        mine_idx = {l: np.ascontiguousarray(sel[lo:lo + n, :padded])
                    for l, sel in selections.items()}
        forced = {"new": n, "sets": mine, "index": mine_idx}
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
        experts = {l: i for l, i in infos.items() if "own" in i}
        row_disputed, row_widest = disputes(experts, mine)
        places += n * len(experts)
        disputed += row_disputed
        widest = max(widest, row_widest)
        sel, differ, gap = index_disputes(infos, mine_idx)
        idx["selected"] += sel
        idx["differ"] += differ
        idx["widest"] = max(idx["widest"], gap)
        facts = dict(logit_check=r["kind"], row=r["row"], new=n,
                     ctx=r["ctx"], max_abs_ref=f"{peak:.4g}",
                     rel_err=f"{err:.4g}", rel_rms=f"{rms:.4g}",
                     places=n * len(experts), disputed=row_disputed,
                     widest_margin=f"{row_widest:.4g}", index_selected=sel,
                     index_differ=differ, index_gap=f"{gap:.4g}")
        if probe:
            low, low_infos = reference_last_logits(
                model, cfg, r["tokens"], padded, forced, limits["bucket"],
                rounded=True)
            p_err = float(np.abs(low - want).max()) / peak
            p_rms = float(np.sqrt(np.mean((low - want) ** 2))) / size
            p_disputed, p_widest = disputes(
                {l: i for l, i in low_infos.items() if "own" in i}, mine)
            _, p_differ, p_gap = index_disputes(low_infos, mine_idx)
            facts.update(float8_rel_err=f"{p_err:.4g}",
                         float8_rel_rms=f"{p_rms:.4g}",
                         float8_disputed=p_disputed,
                         float8_widest_margin=f"{p_widest:.4g}",
                         float8_index_differ=p_differ,
                         float8_index_gap=f"{p_gap:.4g}")
            worst["probe_err"] = max(worst["probe_err"], p_err)
            worst["probe_rms"] = max(worst["probe_rms"], p_rms)
        say(**facts)
    share = disputed / max(places, 1)
    idx_share = idx["differ"] / max(idx["selected"], 1)
    say(logit_limits=f"rel_err<={limits['max_rel_err']} "
        f"rel_rms<={limits['max_rel_rms']}",
        worst_rel_err=f"{worst['err']:.4g}",
        worst_rel_rms=f"{worst['rms']:.4g}",
        selection_limits=f"margin<={selection['epsilon']} "
        f"share<={selection['max_share']}", places=places,
        disputed=disputed, disputed_share=f"{share:.4g}",
        widest_margin=f"{widest:.4g}",
        index_limits=f"gap<={index_selection['epsilon']} "
        f"share<={index_selection['max_share']}",
        index_selected=idx["selected"], index_differ=idx["differ"],
        index_differ_share=f"{idx_share:.4g}",
        index_widest_gap=f"{idx['widest']:.4g}")
    checks = {"logits_finite": finite,
              "logits_within_limits": (
                  worst["err"] <= limits["max_rel_err"]
                  and worst["rms"] <= limits["max_rel_rms"]),
              "selection_disputes_are_near_ties":
                  widest <= selection["epsilon"],
              "selection_dispute_share_within_limit":
                  share <= selection["max_share"],
              "index_disputes_are_near_ties":
                  idx["widest"] <= index_selection["epsilon"],
              "index_dispute_share_within_limit":
                  idx_share <= index_selection["max_share"]}
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
        window_blocks=engine.cfg.num_window_blocks, parameters=params,
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits = wl["logit_check"]
    spy_args = dict(min_decode_ctx=limits["min_decode_ctx"],
                    min_carried_ctx=limits["min_carried_ctx"],
                    rows=limits["rows"])
    spy = None
    if ctx.trace:
        spy = SparseSpy(engine, spans, keep_sizes=True, **spy_args)
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
        spy = SparseSpy(engine, ctx.no_spans, keep_sizes=False, **spy_args)
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
    kernels = {k: [] for k in ("dots3_index", "dots3_sparse_mla",
                               "dots3_window_mla", EXPERT_SCOPE)}
    if kept is not None:
        say(kept_step_after=loop.step_no - warm_steps - engine_steps,
            live_blocks=kept["live_blocks"],
            live_window_blocks=kept["live_window_blocks"],
            rows=[(r["kind"], r["new"], r["ctx"]) for r in kept["rows"]])
        compiled = step_compiled(engine, spy.real, cache_shapes)
        text = compiled.as_text()
        sparse, window, index = (program.custom_calls(text, k)
                                 for k in (SPARSE, WINDOW, INDEX))
        grouped = program.custom_calls(text, GROUPED)
        kernels = {
            "dots3_index": sorted(set(index) | set(
                scoped_instructions(text, INDEX_SCOPES))),
            "dots3_sparse_mla": sparse, "dots3_window_mla": window,
            EXPERT_SCOPE: sorted(set(grouped) | set(
                scoped_instructions(text, (EXPERT_SCOPE,))))}
        say(sparse_latent_calls=len(sparse), window_latent_calls=len(window),
            index_kernel_calls=len(index),
            index_instructions=len(kernels["dots3_index"]),
            expert_instructions=len(kernels[EXPERT_SCOPE]),
            grouped_calls=len(grouped),
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            types = model_cfg["layer_types"]
            full = sum(1 for t in types if t == "full_attention")
            checks["sparse_call_and_index_kernel_once_per_full_layer"] = (
                len(sparse) == len(index) == full)
            checks["window_call_once_per_sliding_layer"] = (
                len(window) == len(types) - full)
            checks["grouped_calls_per_expert_layer"] = len(grouped) == (
                wl["grouped_calls_per_expert_layer"]
                * (len(types) - model_cfg["first_k_dense_replace"]))
        checks.update(compare(model, model_cfg, kept, limits,
                              wl["selection"], wl["index_selection"], say))

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
        kv_blocks_window=snap["kv_blocks_window"],
        window_blocks_released=snap["window_blocks_released"],
        moe_expert_rows=snap["moe_expert_rows"],
        moe_experts_hit=snap["moe_experts_hit"],
        index_visible=snap["index_visible"],
        index_selected=snap["index_selected"])
    return {
        "checks": checks,
        "attempted": len(in_win),
        "failed": sum(1 for r in in_win if r.reason != "length"
                      or len(r.times) != r.want),
        "samples": samples,
        "trace_outer": "router_step",
        "trace_iteration": "engine_step",
        "kernels": kernels,
    }
