"""Runner ``serve_closed``: a closed loop of clients against
``FleetRouter([InProcessReplica(model, EngineConfig(**engine))])``,
stepped by the harness in one thread. Each client submits its next
request the moment its previous one finishes, so the sequence of engine
steps is a pure function of the seed. Tokens are stamped on the
caller's clock when ``router.step()`` returns them.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark import program, reference, stats, traffic

KERNEL = "ragged_paged_attention"


class Record:
    __slots__ = ("rid", "client", "want", "submit", "submit_step", "times",
                 "steps", "reason")

    def __init__(self, rid, client, want, submit, submit_step):
        self.rid, self.client, self.want = rid, client, want
        self.submit, self.submit_step = submit, submit_step
        self.times, self.steps, self.reason = [], [], None


class ClosedLoop:
    def __init__(self, router, replica, stream, clients):
        from paddle_tpu.serving import SamplingParams

        self._params = SamplingParams
        self.router, self.replica, self.stream = router, replica, stream
        self.clients = clients
        self.live, self.done = {}, []
        self.step_no = 0
        self.step_log = []          # (start, end, sequences given a token)
        self.finished_once = set()
        self.on_submit = None

    def submit(self, client):
        rid, prompt, sampling = self.stream.next()
        rec = Record(rid, client, sampling["max_new_tokens"],
                     time.perf_counter(), self.step_no)
        self.live[rid] = rec
        self.router.add_request(rid, prompt, self._params(**sampling))
        if self.on_submit is not None:
            self.on_submit()

    def start(self):
        for c in range(self.clients):
            self.submit(c)

    def pump(self):
        t_a = time.perf_counter()
        outs = self.router.step()
        now = time.perf_counter()
        self.step_no += 1
        rows, ended = 0, []
        for out in outs:
            rec = self.live[out.request_id]
            if out.token is not None:
                rec.times.append(now)
                rec.steps.append(self.step_no)
                rows += 1
            if out.finished:
                rec.reason = out.finish_reason
                ended.append(rec)
        self.step_log.append((t_a, now, rows))
        if not self.replica.alive:
            raise RuntimeError("the replica's engine died") \
                from self.replica.last_error
        for rec in sorted(ended, key=lambda r: r.client):
            self.router.release_request(rec.rid)
            del self.live[rec.rid]
            self.done.append(rec)
            self.finished_once.add(rec.client)
            self.submit(rec.client)

    def records(self):
        return self.done + list(self.live.values())


class StepSpy:
    """Stands in for the engine's compiled step: stamps each dispatch
    as spans and keeps what sizes each dispatch was handed
    (``cu_seqlens``, ``context_lens``, ``num_seqs``); on request keeps
    ONE dispatch's host inputs whole for the kernel-against-reference
    check."""

    def __init__(self, engine, spans, keep_sizes):
        self.engine, self.real = engine, engine._jstep_ragged
        self.spans, self.keep_sizes = spans, keep_sizes
        self.sizes = []             # (time, cu, ctx, num_seqs)
        self.want, self.got = False, None
        engine._jstep_ragged = self

    def __call__(self, *args):
        if self.want and self.got is None:
            self.got = tuple(np.array(args[i]) for i in (3, 6, 7, 8, 9))
        if self.keep_sizes:
            self.sizes.append((time.perf_counter(), np.array(args[7]),
                               np.array(args[8]), int(args[9])))
        self.spans.close("schedule+fill")
        with self.spans("dispatch"):
            out = self.real(*args)
        self.spans.open("fetch+post")
        return out

    def remove(self):
        self.engine._jstep_ragged = self.real


def wrap_engine_step(engine, spans):
    """``--trace 1`` only: a span around ``engine.step`` split at the
    dispatch by the spy: schedule+fill | dispatch | fetch+post."""
    real = engine.step

    def step():
        spans.open("engine_step")
        spans.open("schedule+fill")
        try:
            return real()
        finally:
            # a step that dispatched nothing never reached the spy
            spans.close("fetch+post" if spans.is_open("fetch+post")
                        else "schedule+fill")
            spans.close("engine_step")

    engine.step = step


def engine_step_compiled(engine, real_step, ids, bt, cu, ctx, nseq):
    """The engine's one ragged step, lowered again from the shapes of a
    real dispatch (a persistent-cache hit), for its text and memory."""
    from jax import ShapeDtypeStruct as sds

    s, r = engine.cfg.max_num_seqs, engine._spec_R
    ids, bt, cu, ctx, nseq = (sds(a.shape, a.dtype)
                              for a in (ids, bt, cu, ctx, nseq))
    sampling = (sds((s, 2), np.uint32), sds((s,), np.float32),     # keys, T
                sds((s,), np.int32), sds((s,), np.float32),   # top-k, top-p
                sds((s, r - 1), np.int32), sds((s,), np.int32))   # drafts
    return real_step.lower(
        *program.shapes_of(([p._data for p in engine._params],
                            [b._data for b in engine._buffers],
                            engine._key)),
        ids, *program.shapes_of((engine._kcs, engine._vcs)), bt, cu, ctx,
        nseq, *sampling).compile()


def compare_attention(model, engine, step_inputs, impl, say):
    """Layer 0's ragged attention on one real step's inputs (token ids,
    block tables and lengths as dispatched; q/k/v from the model's own
    projections; the engine's live cache): the program's entry point at
    the engine's full shape against the benchmark's plain reference."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import _rope_apply_at
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    ids, bt, cu, ctx, nseq = step_inputs
    t_total = ids.shape[0]
    layer = model.llama.layers[0]
    attn = layer.self_attn
    h = layer.input_layernorm(model.llama.embed_tokens(
        paddle.to_tensor(ids.reshape(1, t_total))))
    q = attn.q_proj(h)._data.reshape(1, t_total, attn.n_heads, attn.head_dim)
    k = attn.k_proj(h)._data.reshape(1, t_total, attn.n_kv, attn.head_dim)
    v = attn.v_proj(h)._data.reshape(t_total, attn.n_kv, attn.head_dim)
    pos = reference.token_positions(t_total, cu, ctx, nseq)
    rope_at = jnp.asarray(np.maximum(pos, 0))
    q, k = _rope_apply_at(q, k, layer.rope_cos._data[rope_at][None],
                          layer.rope_sin._data[rope_at][None])
    q, k = q[0], k[0]
    kc0, vc0 = engine._kcs[0], engine._vcs[0]
    old_k, old_v = np.asarray(kc0.astype(jnp.float32)), np.asarray(
        vc0.astype(jnp.float32))
    out, _, _ = jax.jit(
        lambda *a: rpa.ragged_paged_attention(*a, impl=impl))(
            q, k, v, kc0, vc0, bt, cu, ctx, nseq)
    out = np.asarray(out.astype(jnp.float32))
    ref = reference.ragged_attention(
        np.asarray(q.astype(jnp.float32)), np.asarray(k.astype(jnp.float32)),
        np.asarray(v.astype(jnp.float32)), old_k, old_v, bt, cu, ctx, nseq,
        1.0 / math.sqrt(attn.head_dim))
    n_valid = int(cu[int(nseq)])
    err = float(np.abs(out - ref).max())
    peak = float(np.abs(ref).max())
    rows = [int(cu[i + 1] - cu[i]) for i in range(int(nseq))]
    say(attention_check=f"{impl} vs benchmark/reference.py", tokens=n_valid,
        seqs=int(nseq), prefill_rows=sum(1 for r in rows if r > 1),
        decode_rows=sum(1 for r in rows if r == 1),
        max_abs_diff=f"{err:.3g}", max_abs_ref=f"{peak:.3g}")
    return {"attention_finite": bool(np.isfinite(out).all()),
            "attention_padding_zero": not out[n_valid:].any(),
            "attention_within_3pct": peak > 0 and err <= 3e-2 * peak}


def run(ctx):
    from paddle_tpu.serving import EngineConfig
    from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

    wl, model_cfg, say = ctx.workload, ctx.config, ctx.say
    ecfg = dict(wl["engine"])
    model = program.build_lm(model_cfg, ecfg["max_model_len"], ctx.seed)
    model.eval()
    replica = InProcessReplica(model, EngineConfig(**ecfg), replica_id="r0")
    router = FleetRouter([replica])
    engine = replica.engine
    impl = wl.get("kernel_impl", "pallas")
    say(ragged_attention_impl=impl, token_budget=engine._ragged_T,
        seq_slots=engine.cfg.max_num_seqs, kv_blocks=engine.cfg.num_blocks,
        donated_cache=engine._donated, built_s=round(ctx.since_start(), 1))

    spans = ctx.spans
    spy = None
    if ctx.trace:
        spy = StepSpy(engine, spans, keep_sizes=True)
        wrap_engine_step(engine, spans)
    loop = ClosedLoop(router, replica, traffic.RequestStream(
        wl["traffic"], model_cfg["vocab_size"], ctx.seed),
        wl["traffic"]["clients"])

    # warm-up: the same loop until every client has finished its first
    # request; compiles or loads the one step and staggers the clients
    loop.start()
    while len(loop.finished_once) < loop.clients:
        with spans("router_step"):
            loop.pump()
    warm_steps = loop.step_no

    # the window; with --trace 1 its last stretch is under the profiler
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

    # after the window: one real mixed step's inputs, the compiled
    # step's text, the kernel against the reference
    if spy is None:
        spy = StepSpy(engine, ctx.no_spans, keep_sizes=False)
    loop.on_submit = lambda: setattr(spy, "want", True)
    guard = loop.step_no + 4096
    while spy.got is None and loop.step_no < guard:
        loop.pump()
    spy.remove()
    checks = {"mixed_step_seen": spy.got is not None}
    compiled = engine_step_compiled(engine, spy.real, *spy.got)
    calls = program.custom_calls(compiled.as_text(), KERNEL)
    say(ragged_custom_calls=len(calls), first=calls[:2],
        step_program_bytes=program.program_bytes(compiled))
    if impl == "pallas":
        checks["kernel_once_per_layer"] = (
            len(calls) == model_cfg["num_hidden_layers"])
    checks.update(compare_attention(model, engine, spy.got, impl, say))

    recs = loop.records()
    in_win = [r for r in loop.done if r.times and t0 <= r.times[-1] <= t1]
    checks["all_finished_length"] = all(
        r.reason == "length" and len(r.times) == r.want for r in loop.done)
    checks["no_compile_in_window"] = compiled_in_window == 0
    checks["no_logits_fetch"] = engine.num_logits_fetches == 0
    win_steps = [s for s in loop.step_log if t0 <= s[1] <= t1]
    samples = {
        "setup_s": setup_s,
        "window_s": ctx.seconds,
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
        prefill_steps=engine.metrics.prefill_steps)
    if ctx.trace:
        # an engine step's span closes inside its router step's: pair them
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
            if traced_from is not None and traced_from <= s[0] <= traced_to]
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
