"""The program's spans (``paddle_tpu.profiler.RecordEvent`` / ``span``)
where the work happens: inside ``LLMEngine.step``, ``FleetRouter.step``,
``TrainStep.__call__`` and the ``DevicePrefetcher``, on the profiler's own
clock (``ptpu:<name>`` events of a ``jax.profiler`` trace, attributes as
typed stats), plus the named scopes of the device regions. CPU, tiny
widths: names, nesting, order and counts; no time here means anything."""
import glob
import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, profiler
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     LlamaPretrainingCriterion)
from paddle_tpu.profiler import (Profiler, ProfilerTarget, RecordEvent,
                                 span)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica

ENGINE_CHILDREN = ["engine.schedule", "engine.fill", "engine.dispatch",
                   "engine.fetch", "engine.post"]


class Traced:
    """A device-trace session of the program's own ``Profiler`` (Python
    tracer off, ``_enter_record``); afterwards ``events`` holds the
    trace's ``ptpu:`` events in start order and ``prof.host_events`` the
    in-memory copies."""

    def __init__(self, trace_dir):
        self.dir, self.events = str(trace_dir), []
        self.prof = Profiler(
            targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
            trace_dir=self.dir, record_op_events=False)

    def __enter__(self):
        self.prof.start()
        return self

    def __exit__(self, *exc):
        self.prof.stop()
        path = max(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("ptpu:"):
                        self.events.append({
                            "name": ev.name[5:], "line": (plane.name, i),
                            "start": ev.start_ns,
                            "end": ev.start_ns + ev.duration_ns,
                            "stats": dict(ev.stats)})
        self.events.sort(key=lambda e: (e["start"], -e["end"]))

    def named(self, name):
        return [e for e in self.events if e["name"] == name]

    def inside(self, outer):
        return [e for e in self.events if e is not outer
                and e["line"] == outer["line"]
                and outer["start"] <= e["start"] and e["end"] <= outer["end"]]


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    return LLMEngine(model, EngineConfig(
        block_size=4, max_num_seqs=4, max_model_len=64,
        max_batched_tokens=16, **kw))


def _requests(vocab, n=6):
    rng = np.random.default_rng(3)
    return [(f"r{i}", [int(t) for t in rng.integers(0, vocab, 5 + 4 * i)],
             SamplingParams(max_new_tokens=3 + i % 3, seed=i,
                            temperature=0.8 if i % 2 else 0.0,
                            top_k=20 if i % 2 else 0))
            for i in range(n)]


def _drain(eng):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 500, "engine failed to converge"


@pytest.fixture(scope="module")
def engine_run(tiny_model, tmp_path_factory):
    """One short ragged-engine run under ``jax.profiler``: six requests
    over four slots and a 16-token budget, so prompts are chunked, two
    requests queue, and steps mix prefill chunks with decode rows."""
    eng = _engine(tiny_model)
    counted = []                      # what record_step was handed
    real = eng.metrics.record_step

    def record_step(kind, *a, **kw):
        counted.append((kind, kw["prompt_tokens"], kw["decode_rows"]))
        return real(kind, *a, **kw)

    eng.metrics.record_step = record_step
    with Traced(tmp_path_factory.mktemp("engine_trace")) as tr:
        for rid, prompt, sp in _requests(tiny_model.config.vocab_size):
            eng.add_request(rid, prompt, sampling=sp)
        _drain(eng)
    return eng, tr, counted


def test_dispatch_counts_the_rows_the_filter_acts_on(tiny_model):
    """``sampled_rows`` = the rows of the dispatch with a temperature
    above zero, as the compiled step was handed them."""
    eng = _engine(tiny_model)
    handed = []
    real = eng._jstep_ragged

    def spy(*args):
        handed.append(int((np.asarray(args[11]) > 0).sum()))   # stemp
        return real(*args)

    eng._jstep_ragged = spy
    prof = Profiler(record_op_events=False).start()
    try:
        for rid, prompt, sp in _requests(tiny_model.config.vocab_size):
            eng.add_request(rid, prompt, sampling=sp)
        _drain(eng)
    finally:
        prof.stop()
    got = [(e["args"]["sampled_rows"], e["args"]["rows"])
           for e in prof.host_events if e["name"] == "engine.dispatch"]
    assert [s for s, _ in got] == handed
    assert any(0 < s < rows for s, rows in got)     # mixed with greedy rows


# -- (a) the primitive ------------------------------------------------------
def test_nested_spans_keep_parent_id_and_args_on_two_threads(tmp_path):
    def worker():
        with span("producer", batch=7):
            with RecordEvent("producer.inner", kind="stage"):
                time.sleep(0.001)

    prof = Profiler().start()
    t = threading.Thread(target=worker)
    with span("outer", step=3):
        t.start()
        with span("inner", rows=2, ratio=0.5):
            time.sleep(0.001)
        with span("inner", rows=4):
            pass
        t.join(timeout=30)
    assert not t.is_alive()
    prof.stop()
    by = {}
    for e in prof.host_events:
        by.setdefault(e["name"], []).append(e)
    outer, = by["outer"]
    assert outer["parent"] is None and outer["args"] == {"step": 3}
    assert [e["parent"] for e in by["inner"]] == [outer["id"]] * 2
    assert [e["args"] for e in by["inner"]] == [
        {"rows": 2, "ratio": 0.5}, {"rows": 4}]
    # the other thread's spans have their own stack: its root has no
    # parent though "outer" was open on the main thread at the time
    producer, = by["producer"]
    assert producer["parent"] is None and producer["args"] == {"batch": 7}
    assert by["producer.inner"][0]["parent"] == producer["id"]
    assert producer["tid"] != outer["tid"]
    ids = [e["id"] for e in prof.host_events]
    assert len(set(ids)) == len(ids) == 5
    # the chrome export carries the same tree
    path = prof.export(str(tmp_path / "host.json"))
    with open(path) as f:
        exported = {e["id"]: e for e in json.load(f)["traceEvents"]}
    assert exported[by["producer.inner"][0]["id"]]["parent"] == producer["id"]
    assert exported[by["inner"][0]["id"]]["args"] == {"rows": 2,
                                                      "ratio": 0.5}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in exported.values())
    # self time: a span's duration minus what its children cover
    stats = prof.summary(print_table=False)
    inner_ms = sum(e["dur"] for e in by["inner"]) / 1e3
    assert stats["outer"]["self_ms"] == pytest.approx(
        stats["outer"]["total_ms"] - inner_ms)
    assert stats["inner"]["self_ms"] == pytest.approx(
        stats["inner"]["total_ms"])
    assert 0 <= stats["outer"]["self_ms"] < stats["outer"]["total_ms"]


def test_span_set_joins_attributes_known_at_exit(tmp_path):
    with Traced(tmp_path) as tr:
        with span("post", rows=4) as s:
            s.set(emitted=3, finished=1)
    want = {"rows": 4, "emitted": 3, "finished": 1}
    assert tr.prof.host_events[-1]["args"] == want
    assert tr.named("post")[0]["stats"] == want


def test_span_decorator_and_begin_end():
    @RecordEvent("decorated", kind="fn")
    def f(x):
        return x + 1

    prof = Profiler().start()
    assert f(1) == 2 and f(2) == 3
    ev = RecordEvent("by_hand")
    ev.begin()
    ev.end()
    ev.end()                                # a second end is a no-op
    prof.stop()
    names = [e["name"] for e in prof.host_events]
    assert names == ["decorated", "decorated", "by_hand"]
    assert prof.host_events[0]["args"] == {"kind": "fn"}


def test_span_costs_microseconds_with_no_session():
    """Spans stay in the code path unconditionally: 10,000 enter/exits
    with attributes, nothing recording, under 5 us each (measured ~1.5;
    the bound is loose on purpose)."""
    for i in range(1000):
        with span("warm", step=i):
            pass
    t0 = time.perf_counter()
    for i in range(10_000):
        with span("engine.dispatch", step=i, rows=16, q_tokens=497,
                  kind="mixed"):
            pass
    assert (time.perf_counter() - t0) / 10_000 < 5e-6


# -- (b) the engine step ----------------------------------------------------
def test_engine_step_has_its_five_children_nested_and_in_order(engine_run):
    eng, tr, counted = engine_run
    steps = tr.named("engine.step")
    assert len(steps) == eng.metrics.engine_steps == len(counted) >= 6
    assert [s["stats"]["step"] for s in steps] == list(range(len(steps)))
    for s in steps:
        kids = [e for e in tr.inside(s) if e["name"].startswith("engine.")]
        assert [k["name"] for k in kids] == ENGINE_CHILDREN
        assert all(a["end"] <= b["start"] for a, b in zip(kids, kids[1:]))
        covered = sum(k["end"] - k["start"] for k in kids)
        assert covered >= 0.9 * (s["end"] - s["start"])


def test_dispatch_attributes_are_the_batch_the_metrics_counted(engine_run):
    eng, tr, counted = engine_run
    got = [d["stats"] for d in tr.named("engine.dispatch")]
    # no drafts and no recompute in this run: a step's tokens are its
    # prompt tokens and one per decode row
    assert [(d["kind"], d["q_tokens"] - d["decode_rows"], d["decode_rows"])
            for d in got] == counted
    assert [d["step"] for d in got] == list(range(len(got)))
    assert sum(n for _, n, _ in counted) == eng.metrics.num_prompt_tokens
    assert all(d["prefill_rows"] + d["decode_rows"] == d["rows"]
               and 0 < d["q_tokens"] <= 16 and d["ctx_tokens"] >= d["q_tokens"]
               and d["attempt"] == 0 for d in got)
    # one compiled shape: only the first dispatch met a new one
    assert [d["cold"] for d in got] == [1] + [0] * (len(got) - 1)
    assert {d["kind"] for d in got} >= {"mixed"}
    posts = [p["stats"] for p in tr.named("engine.post")]
    assert sum(p["emitted"] for p in posts) == \
        eng.metrics.num_generated_tokens
    assert sum(p["finished"] for p in posts) == eng.metrics.num_finished == 6


def test_an_engine_step_that_dispatches_nothing_has_schedule_only(
        tiny_model, tmp_path):
    eng = _engine(tiny_model)
    with Traced(tmp_path) as tr:
        assert eng.step() == []
    step, = tr.named("engine.step")
    assert [e["name"] for e in tr.inside(step)] == ["engine.schedule"]


# -- (c) one identifier follows a request ------------------------------------
def test_request_events_share_the_request_id(engine_run):
    eng, tr, _ = engine_run
    marks = ["request.arrive", "request.scheduled", "request.first_token",
             "request.finish"]
    for rid, prompt, sp in _requests(eng.model.config.vocab_size):
        mine = [e for e in tr.events if e["name"].startswith("request.")
                and e["stats"]["request_id"] == rid]
        assert [e["name"] for e in mine] == marks
        arrive, sched, first, finish = (e["stats"] for e in mine)
        assert arrive["prompt_tokens"] == len(prompt)
        assert finish["reason"] == "length"
        assert finish["generated"] == sp.max_new_tokens
        req = eng.get_request(rid)
        assert req.arrival_time <= req.first_scheduled_time \
            <= req.first_token_time <= req.finish_time
        assert sched["waited_ms"] == pytest.approx(
            (req.first_scheduled_time - req.arrival_time) * 1e3, abs=1e-3)
        assert first["ttft_ms"] >= sched["waited_ms"] >= 0
    # four slots and a 16-token budget for six requests: first come,
    # first scheduled, and the last ones waited for a slot
    waited = [e["stats"]["waited_steps"]
              for e in tr.named("request.scheduled")]
    assert waited == sorted(waited) and waited[0] == 0 and waited[-1] > 1
    snap = eng.metrics.snapshot()
    assert 0 < snap["queue_ms_p90"] <= snap["ttft_ms_p90"]


def test_aborts_and_rejections_finish_in_the_trace_too(tiny_model, tmp_path):
    eng = _engine(tiny_model)
    with Traced(tmp_path) as tr:
        eng.add_request("gone", [1, 2, 3],
                        sampling=SamplingParams(max_new_tokens=4))
        assert eng.abort_request("gone")
        eng.start_drain("test")
        eng.add_request("late", [1, 2, 3])      # a draining engine rejects
    assert [(e["stats"]["request_id"], e["stats"]["reason"])
            for e in tr.named("request.finish")] == [
                ("gone", "aborted:user"), ("late", "rejected")]
    assert eng.finish_counts == {"aborted:user": 1, "rejected": 1}


# -- the router ------------------------------------------------------------
def test_router_step_splits_into_control_replica_and_collect(tiny_model,
                                                              tmp_path):
    cfg = dict(block_size=4, max_num_seqs=4, max_model_len=64)
    router = FleetRouter([InProcessReplica(tiny_model, EngineConfig(**cfg),
                                           replica_id="r0")])
    with Traced(tmp_path) as tr:
        rid = router.add_request("q0", [5, 6, 7, 8], SamplingParams(
            max_new_tokens=3))
        outs = []
        while router.has_unfinished():
            outs.append(router.step())
    steps = tr.named("router.step")
    assert [s["stats"]["step"] for s in steps] == list(range(len(outs)))
    for s, out in zip(steps, outs):
        kids = [e for e in tr.inside(s) if e["name"].split(".")[0]
                in ("router", "replica")]
        assert [k["name"] for k in kids] == [
            "router.control", "replica.step", "router.collect"]
        assert kids[1]["stats"] == {"replica": "r0"}
        assert kids[2]["stats"]["replica"] == "r0"
        assert kids[2]["stats"]["outputs"] >= len(out)
        # the engine's step is the replica step's child
        inner = [e["name"] for e in tr.inside(kids[1])]
        assert inner[0] == "engine.step"
    assert router.release_request(rid).generated


# -- (d) the train step behind the prefetcher --------------------------------
class _Tokens(Dataset):
    def __init__(self, vocab, n=10, seq=16):
        self.rows = np.random.default_rng(0).integers(
            0, vocab, (n, seq + 1)).astype(np.int32)

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i][:-1], self.rows[i][1:]


def test_train_step_and_prefetcher_spans(tmp_path):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    loader = DataLoader(_Tokens(model.config.vocab_size), batch_size=2,
                        shuffle=False, use_device_prefetch=True)
    losses = []
    with Traced(tmp_path) as tr:
        for xb, yb in loader:
            losses.append(step(xb, yb))
        final = float(losses[-1]._data)
    assert len(losses) == 5 and np.isfinite(final)
    steps, dispatches = tr.named("train.step"), tr.named("train.dispatch")
    assert [s["stats"]["step"] for s in steps] == [0, 1, 2, 3, 4]
    assert [d["stats"]["cold"] for d in dispatches] == [1, 0, 0, 0, 0]
    for s, d in zip(steps, dispatches):
        assert tr.inside(s) == [d]
    stages, loads = tr.named("prefetch.stage"), tr.named("prefetch.load")
    assert [e["stats"]["batch"] for e in stages] == [0, 1, 2, 3, 4]
    # the sixth load is the one that found the loader exhausted
    assert [e["stats"]["batch"] for e in loads] == [0, 1, 2, 3, 4, 5]
    producer = {e["line"] for e in stages + loads}
    assert len(producer) == 1 and steps[0]["line"] not in producer
    # the consumer waits on the main thread: one wait a batch + the end
    waits = tr.named("prefetch.wait")
    assert len(waits) == 6 and {w["line"] for w in waits} == {
        steps[0]["line"]}


# -- (e) nothing without a profiler; nothing changes with one -----------------
def test_nothing_is_recorded_with_no_profiler_and_no_session():
    done = Profiler().start()
    done.stop()
    before = list(profiler._recorder.events)
    with span("unseen", step=1):
        with span("unseen.inner"):
            pass
    assert profiler._recorder.events == before
    assert not profiler._recorder.active
    assert done.host_events == []


def test_tokens_are_the_same_with_a_profiler_recording(tiny_model):
    def serve():
        eng = _engine(tiny_model)
        reqs = _requests(tiny_model.config.vocab_size)
        for rid, prompt, sp in reqs:
            eng.add_request(rid, prompt, sampling=sp)
        _drain(eng)
        return [eng.get_request(rid).generated for rid, _, _ in reqs]

    plain = serve()
    prof = Profiler(record_op_events=False).start()
    recorded = serve()
    prof.stop()
    assert recorded == plain and all(plain)
    names = {e["name"] for e in prof.host_events}
    assert {"engine.step", *ENGINE_CHILDREN, "request.finish"} <= names
    by_id = {e["id"]: e for e in prof.host_events}
    assert all(by_id[e["parent"]]["name"] == "engine.step"
               for e in prof.host_events if e["name"] in ENGINE_CHILDREN)


# -- (f) named scopes of the device regions -----------------------------------
def _engine_step_text(model):
    eng = _engine(model)
    seen = []
    real = eng._jstep_ragged

    def spy(*args):
        seen.append(args)
        return real(*args)

    eng._jstep_ragged = spy
    eng.add_request("a", [1, 2, 3, 4, 5])
    eng.step()
    return real.lower(*seen[0]).as_text(debug_info=True)


def _hybrid_engine_step_text(_):
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)

    paddle.seed(0)
    model = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    model.eval()
    return _engine_step_text(model)


def _train_step_text(_):
    cfg = LlamaConfig.tiny(use_flash_attention="interpret")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    return step._jitted.lower(
        1, step._carry, [p._data for p in step._params], step._slots,
        [b._data for b in step._buffers], jnp.float32(1e-3),
        step._scaler_state, tokens, tokens).as_text(debug_info=True)


@pytest.mark.parametrize("text_of,scopes", [
    (_engine_step_text, ("attention", "kv_update", "sampler")),
    (_train_step_text, ("attention", "lm_head_loss", "optimizer")),
    (_hybrid_engine_step_text, ("attention", "kv_update", "sampler")),
], ids=["engine_ragged_step", "train_step", "engine_hybrid_step"])
def test_device_regions_are_named_in_the_lowered_step(tiny_model, text_of,
                                                      scopes):
    text = text_of(tiny_model)
    # a scope is one component of an op's name; under autodiff it comes
    # wrapped: jit(step_fn)/transpose(jvp(attention))/flash_attention_bwd_dq
    for scope in scopes:
        assert re.search(rf'loc\("[^"]*[/(]{scope}[)/]', text), scope
