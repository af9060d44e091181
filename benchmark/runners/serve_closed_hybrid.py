"""Runner ``serve_closed_hybrid``: ``serve_closed``'s closed loop against
a model that says what it caches (``cache_spec()``: the SambaY
decoder-hybrid-decoder of ``paddle_tpu/models/phi4flash.py``), through
the same ``FleetRouter([InProcessReplica(model, EngineConfig(**engine))])``.

From ``serve_closed`` it IMPORTS, and does not copy: ``ClosedLoop`` and
``Record`` (the loop and its stamps), ``StepSpy`` (the engine's step keeps
ids, block table, ``cu_seqlens``, ``context_lens`` and ``num_seqs`` at the
argument positions 3, 6, 7, 8, 9 it reads) and ``wrap_engine_step``.
``serve_closed`` reduces its samples inside ``run`` and has no function
to import for that, so ``reduce_samples`` here states the same reduction
(same keys, same ``stats`` calls).

``correct`` (all outside the window): every finished request ended
``length`` with exactly its ``max_new_tokens``; no program compiled inside
the window; ``num_logits_fetches == 0``; the ``ragged_paged_attention``
custom call stands in the compiled step once per attention layer by the
padded-query route (window + full + cross layers: 16); and **logits**:
after the window the spy keeps ONE real step's inputs and a copy of the
whole cache as it was before that step (a step that holds at least one
row continuing a chunked prompt with carried state, one decode row past
the window, one row started from zero), runs the model's own
``forward_ragged`` on them, layer kind by layer kind, and compares the
logits of ``logit_check.rows`` of those rows with
``benchmark/reference_phi4flash.py``'s full forward over each row's whole
token history (float32, ``highest``, one layer's weights upcast at a
time). Two limits, both as shares of the reference's own size, from the
workload file: the largest difference over the largest logit, and the
root-mean-square difference over the root-mean-square logit. With
``logit_check.probe`` (rehearsals and the one probe run, never a cell)
the reference is computed once more with every weight and every layer's
input rounded to float8_e4m3, the nearest precision below the
configuration's bfloat16: that reading has to FAIL the limits.
"""
from __future__ import annotations

import functools
import re
import time

import numpy as np

from benchmark import program, reference_phi4flash as ref, stats, traffic
from benchmark.runners.serve_closed import (ClosedLoop, Record, StepSpy,
                                            wrap_engine_step)

KERNEL = "ragged_paged_attention"
SCOPES = ("ssm_scan", "ssm_conv")
REF_KEYS = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "hidden_size", "sliding_window",
            "layer_norm_eps")
MODEL_KEYS = REF_KEYS + ("vocab_size", "intermediate_size", "mb_per_layer",
                         "max_position_embeddings", "tie_word_embeddings",
                         "mamba_d_state", "mamba_d_conv", "mamba_expand",
                         "mamba_dt_rank")
__all__ = ["run", "Record"]


def build_model(model, seed, impl=None):
    """The configuration through the program's own model class, weights
    drawn on the device from ``seed`` in the dtype they are served in."""
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)

    paddle.seed(seed % (2 ** 31 - 1))
    paddle.set_default_dtype(model["torch_dtype"])
    try:
        return Phi4FlashForCausalLM(Phi4FlashConfig(
            ragged_attn_impl=impl, **{k: model[k] for k in MODEL_KEYS}))
    finally:
        paddle.set_default_dtype("float32")


def row_kinds(cu, ctx, nseq, window):
    """Live rows of one dispatch by what the logit check wants of them:
    ``carried`` (a chunk continuing a prompt: state loaded), ``decode``
    (one token at a position past the window), ``fresh`` (started from
    zero). {kind: [(row, new tokens, context length)]}, shortest first."""
    kinds = {"carried": [], "decode": [], "fresh": []}
    for i in range(int(nseq)):
        n, c = int(cu[i + 1]) - int(cu[i]), int(ctx[i])
        if n <= 0:
            continue
        if c - n == 0:
            kinds["fresh"].append((i, n, c))
        elif n > 1:
            kinds["carried"].append((i, n, c))
        elif c - 1 >= window:
            kinds["decode"].append((i, n, c))
    for rows in kinds.values():
        rows.sort(key=lambda r: r[2])
    return kinds


class HybridSpy(StepSpy):
    """``StepSpy`` that, when asked, keeps the first step holding all
    three kinds of row WHOLE: every host input, a copy of the cache as it
    was before the step (the step donates and overwrites the original:
    recurrent state has no 'before' afterwards), and the token history of
    the rows to compare."""

    def __init__(self, engine, spans, keep_sizes, window, rows, max_ctx):
        super().__init__(engine, spans, keep_sizes)
        self.window, self.n_rows, self.max_ctx = window, rows, max_ctx
        self.kept = None

    def __call__(self, *args):
        if self.want and self.kept is None:
            kinds = row_kinds(args[7], args[8], args[9], self.window)
            if all(kinds.values()):
                self.kept = self.keep(args, kinds)
        return super().__call__(*args)

    def keep(self, args, kinds):
        import jax
        import jax.numpy as jnp

        # the shortest row of each kind, then the longest decode rows
        # the reference can hold
        rows = [kinds[k][0] + (k,) for k in ("carried", "decode", "fresh")]
        rows += [r + ("decode",) for r in reversed(kinds["decode"][1:])
                 if r[2] <= self.max_ctx]
        bm = self.engine.block_manager
        by_slot = {bm.state_slot(r.request_id): r
                   for r in self.engine.scheduler.running}
        slots = np.asarray(args[5]["slots"])
        picked = []
        for i, n, c, kind in rows[:self.n_rows]:
            req = by_slot[int(slots[i])]
            picked.append({"row": i, "new": n, "ctx": c, "kind": kind,
                           "tokens": [int(t) for t in req.tokens[:c]]})
        return {
            "cache": jax.tree.map(jnp.copy, args[4]),
            "tables": {k: np.array(v) for k, v in args[5].items()},
            "ids": np.array(args[3]), "bt": np.array(args[6]),
            "cu": np.array(args[7]), "ctx": np.array(args[8]),
            "nseq": np.int32(args[9]),
            "sampling": tuple(np.array(a) for a in args[10:]),
            "rows": picked}


def step_compiled(engine, real_step, kept):
    """The engine's one step, lowered again from the shapes of the kept
    dispatch (a persistent-cache hit), for its text and memory."""
    from jax import ShapeDtypeStruct as sds

    def host(a):
        return sds(a.shape, a.dtype)

    return real_step.lower(
        *program.shapes_of(([p._data for p in engine._params],
                            [b._data for b in engine._buffers],
                            engine._key)),
        host(kept["ids"]), program.shapes_of(kept["cache"]),
        {k: host(v) for k, v in kept["tables"].items()}, host(kept["bt"]),
        host(kept["cu"]), host(kept["ctx"]), host(kept["nseq"]),
        *(host(a) for a in kept["sampling"])).compile()


def scoped_instructions(compiled_text, scopes):
    """HLO instruction names whose ``op_name`` carries one of the named
    scopes, control flow left out (a ``while`` encloses its body's ops,
    which are listed themselves). The trace names ops by these."""
    names = []
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.+?\s+([\w\-]+)\(",
                     line)
        if not m or m.group(2) in ("while", "conditional", "call",
                                   "parameter", "get-tuple-element",
                                   "tuple", "constant", "bitcast"):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        if op and any(s in op.group(1) for s in scopes):
            names.append(m.group(1))
    return names


def reference_last_logits(model, cfg, tokens, padded, round_to=None):
    """The reference's logits at the last position of ``tokens``: the
    whole history through ``reference_phi4flash.run_layer``, float32, one
    layer's weights upcast at a time, one compiled program a layer kind
    at the one ``padded`` length (padding follows the sequence: causal
    layers never see it). ``round_to``: every weight and every layer's input rounded
    to that dtype first (the probe of the nearest precision below)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rcfg = {k: cfg[k] for k in REF_KEYS}
    n = rcfg["num_hidden_layers"]

    def lower(a):
        a = a.astype(f32)
        return a if round_to is None else a.astype(round_to).astype(f32)

    layer = _layer_program(tuple(rcfg.items()))
    t = len(tokens)
    ids = np.zeros((padded,), np.int32)
    ids[:t] = tokens
    embed = model.embed_tokens.weight._data
    x = lower(embed[jnp.asarray(ids)])
    carry = {}
    for l, lay in enumerate(model.layers):
        p = {k: lower(v) for k, v in lay.weights().items()}
        x, carry = layer(ref.layer_kind(l, n), p, lower(x), carry,
                         lam_init=jnp.float32(ref.lambda_init(l)),
                         emits_memory=(l == n // 2))
    nw, nb = (lower(model.final_norm.weight._data),
              lower(model.final_norm.bias._data))
    head = jax.jit(functools.partial(ref.head, cfg=rcfg))
    rows = embed.shape[0]
    step = -(-rows // 8)
    return np.concatenate([
        np.asarray(head(x[t - 1:t], lower(embed[a:a + step]), nw, nb))[0]
        for a in range(0, rows, step)])


@functools.lru_cache(maxsize=None)
def _layer_program(cfg_items):
    """The reference's ``run_layer`` for one configuration, compiled once
    a layer kind (its first argument) and input shape."""
    import jax

    return jax.jit(functools.partial(ref.run_layer, cfg=dict(cfg_items)),
                   static_argnums=(0,), static_argnames=("emits_memory",))


def compare_logits(model, cfg, kept, limits, say):
    """The model's own ``forward_ragged`` on the kept step's inputs and
    the cache as it was before that step, against the reference over
    each picked row's whole history."""
    import jax.numpy as jnp

    logits, _ = model.forward_ragged(
        kept["ids"], kept.pop("cache"), kept["tables"], kept["bt"],
        kept["cu"], kept["ctx"], kept["nseq"])
    logits = np.asarray(logits.astype(jnp.float32))
    probe = jnp.float8_e4m3fn if limits.get("probe") else None
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
        if probe is not None:
            low = reference_last_logits(model, cfg, r["tokens"], padded,
                                        round_to=probe)
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
    if probe is not None:
        # the nearest precision below has to come out as not correct,
        # by one of the limits
        checks["float8_reference_fails"] = (
            worst["probe_err"] > limits["max_rel_err"]
            or worst["probe_rms"] > limits["max_rel_rms"])
    return checks


def reduce_samples(loop, spy, spans, setup_s, seconds, t0, t1, traced,
                   trace_on):
    """``serve_closed.run``'s reduction of the loop's stamps to samples,
    key for key (that runner has it inline)."""
    recs = loop.records()
    win_steps = [s for s in loop.step_log if t0 <= s[1] <= t1]
    samples = {
        "setup_s": setup_s,
        "window_s": seconds,
        "out_tokens": sum(stats.count_in(r.times, t0, t1) for r in recs),
        "ttft_ms": [(r.times[0] - r.submit) * 1e3 for r in recs
                    if r.times and t0 <= r.times[0] <= t1],
        "itl_ms": [g * 1e3 for r in recs
                   for g in stats.gaps_ending_in(r.times, t0, t1)],
        "ttft_steps": [r.steps[0] - r.submit_step for r in recs
                       if r.times and t0 <= r.times[0] <= t1],
        "steps": len(win_steps),
        "rows": sum(s[2] for s in win_steps),
    }
    if trace_on:
        walls, inner = [], None
        for name, a, b in spans.records:
            if name == "engine_step":
                inner = b - a
            elif name == "router_step":
                if inner is not None and a >= t0 and b <= t1:
                    walls.append((b - a, inner))
                inner = None
        samples["step_wall_ms"] = [e * 1e3 for _, e in walls]
        samples["router_ms"] = [(r - e) * 1e3 for r, e in walls]
        samples["slice_sizes"] = [
            s[1:] for s in spy.sizes
            if traced[0] is not None and traced[0] <= s[0] <= traced[1]]
    return samples, win_steps


def run(ctx):
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
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        window_blocks=engine.cfg.num_window_blocks,
        state_slots=engine.block_manager.state_slots,
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    limits = wl["logit_check"]
    spy_args = dict(window=model_cfg["sliding_window"], rows=limits["rows"],
                    max_ctx=limits["max_ctx"])
    spy = None
    if ctx.trace:
        spy = HybridSpy(engine, spans, keep_sizes=True, **spy_args)
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

    # after the window: one real step with all three kinds of row, whole
    if spy is None:
        spy = HybridSpy(engine, ctx.no_spans, keep_sizes=False, **spy_args)
    spy.want = True
    guard = loop.step_no + limits.get("guard_steps", 1500)
    while spy.kept is None and loop.step_no < guard:
        loop.pump()
    spy.remove()
    kept = spy.kept
    # the engine serves nothing after this: its own cache makes room for
    # the copy's functional updates and the float32 reference
    engine._cache = None
    checks = {"mixed_step_seen": kept is not None}
    calls, ssm = [], []
    if kept is not None:
        say(kept_step_after=loop.step_no - warm_steps - engine_steps,
            rows=[(r["kind"], r["new"], r["ctx"]) for r in kept["rows"]])
        compiled = step_compiled(engine, spy.real, kept)
        text = compiled.as_text()
        calls = program.custom_calls(text, KERNEL)
        ssm = scoped_instructions(text, SCOPES)
        say(ragged_custom_calls=len(calls), first=calls[:2],
            ssm_instructions=len(ssm),
            step_program_bytes=program.program_bytes(compiled))
        if impl == "pallas":
            kinds = [ref.layer_kind(l, model_cfg["num_hidden_layers"])
                     for l in range(model_cfg["num_hidden_layers"])]
            checks["kernel_once_per_attention_layer"] = len(calls) == sum(
                kinds.count(k) for k in ("window", "full", "cross"))
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
        window_blocks_released=snap["window_blocks_released"],
        kv_blocks_full=snap["kv_blocks_full"],
        kv_blocks_window=snap["kv_blocks_window"],
        state_slots_in_use=snap["state_slots_in_use"])
    return {
        "checks": checks,
        "attempted": len(in_win),
        "failed": sum(1 for r in in_win if r.reason != "length"
                      or len(r.times) != r.want),
        "samples": samples,
        "trace_outer": "router_step",
        "trace_iteration": "engine_step",
        "kernels": {KERNEL: calls, "ssm": ssm},
    }
