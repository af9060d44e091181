"""Jamba (Mamba-1 with inner norms beside rope-less multi-query attention)
held to the plain reference (refs/jamba_ref.py), and prefix reuse over
recurrent state: snapshots of the state at block boundaries, kept beside
the prefix trie. Tiny widths with the published layout rule: 6 layers,
attention on layers 1 and 4, four query heads on one K/V head, block 4.
Logits, not tokens, wherever the engine's inputs can be replayed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.serving.request import Request
from paddle_tpu.serving.scheduler import Scheduler, SchedulerConfig
from refs import jamba_ref as ref

BS = 4
NORMS = ("norm1_w", "norm2_w", "final_norm_w", "dt_norm", "b_norm",
         "c_norm")


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def build(norms="random"):
    paddle.seed(3)
    m = JambaForCausalLM(JambaConfig.tiny())
    m.eval()
    rng = np.random.default_rng(0)
    for name, p in m.named_parameters():
        a = np.asarray(p._data)
        if name.endswith(NORMS) and norms == "random":
            p._data = jnp.asarray(1 + 0.3 * rng.standard_normal(a.shape),
                                  a.dtype)
        elif name.endswith("conv_b"):
            p._data = jnp.asarray(0.1 * rng.standard_normal(a.shape),
                                  a.dtype)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def ref_params(m):
    return {"embed": m.embed_tokens.weight._data,
            "norm_w": m.final_norm_w._data,
            "layers": [lay.weights() for lay in m.layers]}


def ref_cfg(m):
    return {k: getattr(m.config, k) for k in ref.CFG_KEYS}


_REF = {}


def ref_logits(m, tokens):
    key = (id(m), tuple(tokens))
    if key not in _REF:
        with jax.default_matmul_precision("highest"):
            _REF[key] = np.asarray(ref.forward(
                ref_params(m), np.asarray(tokens), ref_cfg(m)))
    return _REF[key]


def prompts_with(prefix, tails, seed=1):
    rng = np.random.default_rng(seed)
    return {f"r{i}": list(prefix) + [int(t) for t in rng.integers(1, 160, n)]
            for i, n in enumerate(tails)}


# -- the model against the reference ------------------------------------------
@pytest.mark.parametrize("norms", ["ones", "random"])
def test_forward_matches_reference(norms):
    m = build(norms)
    toks = np.random.default_rng(2).integers(0, 160, (2, 37))
    got = np.asarray(m.forward(toks)._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(m, toks[b]),
                                   atol=2e-4, rtol=0)


def test_inner_norm_weights_reach_the_logits():
    """The three norms inside the mixer are not the identity: the same
    model with their weights at 1 gives other logits, so a dropped norm
    weight would show against the reference."""
    a, b = build("random"), build("random")
    for lay in b.layers:
        for name in ("dt_norm", "b_norm", "c_norm"):
            if hasattr(lay, name):
                p = getattr(lay, name)
                p._data = jnp.ones_like(p._data)
    toks = np.random.default_rng(2).integers(0, 160, (1, 21))
    da = np.asarray(a.forward(toks)._data)
    db = np.asarray(b.forward(toks)._data)
    assert np.abs(da - db).max() > 1e-2
    np.testing.assert_allclose(db[0], ref_logits(b, toks[0]), atol=2e-4,
                               rtol=0)


def test_config_refuses_what_it_does_not_build():
    with pytest.raises(ValueError, match="num_experts"):
        JambaConfig.tiny(num_experts=16)
    with pytest.raises(ValueError, match="sliding_window"):
        JambaConfig.tiny(sliding_window=128)
    with pytest.raises(ValueError, match="mamba_proj_bias"):
        JambaConfig.tiny(mamba_proj_bias=True)


def test_published_layout_and_parameter_count():
    """Attention on layers 7 and 21 of 28; the parameter count of the
    published widths, from the shapes alone (nothing is built)."""
    c = JambaConfig()
    kinds = [c.layer_kind(l) for l in range(c.num_hidden_layers)]
    assert [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21]
    h, e, i = c.hidden_size, c.d_inner, c.intermediate_size
    n, r = c.mamba_d_state, c.mamba_dt_rank
    mlp = 3 * h * i + 2 * h
    mamba = (h * 2 * e + c.mamba_d_conv * e + e + e * (r + 2 * n) + r
             + 2 * n + r * e + e + e * n + e + e * h)
    attn = 2 * h * h + 2 * h * c.head_dim * c.num_key_value_heads
    total = (26 * (mamba + mlp) + 2 * (attn + mlp) + c.vocab_size * h + h)
    assert total == 3_029_337_472
    spec = JambaForCausalLM.cache_spec(type("M", (), {"config": c})())
    assert spec["kv_shape"] == (128,)
    assert [lay["kind"] for lay in spec["layers"]].count("state") == 26


# -- rep 20 on one K/V head through the kernel ---------------------------------
@pytest.mark.parametrize("new,ctx_live,mb,t", [
    ([17, 1, 6], [25, 30, 6], 4, 24),
    # 40 decode rows: one 64-row q tile holds 40 slots, each computed on
    # its own 20 product rows; then 39 of them beside a chunk
    ([1] * 40, [3 + 2 * i for i in range(40)], 12, 64),
    ([1] * 39 + [9], [5 + 2 * i for i in range(39)] + [40], 12, 64),
], ids=["chunk-decode-chunk", "forty-decode-rows", "forty-rows-and-chunk"])
def test_twenty_query_heads_on_one_folded_kv_head_interpreted(new, ctx_live,
                                                              mb, t):
    rng = np.random.default_rng(0)
    s, h, d, bs = len(new), 20, 128, 8
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((t, h, d)), f32)
    k = jnp.asarray(rng.standard_normal((t, 1, d)), f32)
    v = jnp.asarray(rng.standard_normal((t, 1, d)), f32)
    kc = jnp.asarray(rng.standard_normal((s * mb, bs, d)), f32)
    vc = jnp.asarray(rng.standard_normal((s * mb, bs, d)), f32)
    bt = jnp.asarray(rng.permutation(s * mb).reshape(s, mb), jnp.int32)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(new)]), jnp.int32)
    ctx = jnp.asarray(ctx_live, jnp.int32)
    outs = [ragged_paged_attention(q, k, v, kc, vc, bt, cu, ctx,
                                   jnp.int32(s), impl=impl)[0]
            for impl in ("ref", "interpret")]
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[0]),
                               atol=2e-5, rtol=0)


# -- the engine's ragged path ------------------------------------------------------
class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` (outside any jit) on the step's
    own inputs and the cache as it is (state slots loaded from their
    snapshots as the step will), and keeps every live row's logits by
    request and context length."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits, self.restored = {}, []
        engine._jstep_ragged = self

    def __call__(self, *args):
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        tables = dict(tables)
        copies = tables.pop("state_copies", None)
        if copies is not None:
            cache, snaps = cache
            cache, snaps = list(cache), iter(snaps)
            for l, c in enumerate(cache):
                if isinstance(c, dict):
                    snap = next(snaps)
                    for src, dst in copies[0][1:copies[0][0, 0] + 1]:
                        c = {k: c[k].at[dst].set(snap[k][src]) for k in c}
                    cache[l] = c
            self.restored.append(int(copies[0][0, 0]))
        lg, _ = self.engine.model.forward_ragged(ids, cache, tables, bt,
                                                 cu, ctx, nseq)
        lg = np.asarray(lg)
        running = {self.engine.block_manager.state_slot(r.request_id):
                   r.request_id for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            rid = running[int(tables["slots"][i])]
            self.logits[(rid, int(ctx[i]))] = lg[i]
        return self.real(*args)


def serve(model, prompts, new_tokens=5, engine=None, **ecfg):
    kw = dict(block_size=BS, max_num_seqs=3, max_model_len=96,
              max_batched_tokens=16)
    kw.update(ecfg)
    eng = engine or LLMEngine(model, EngineConfig(**kw))
    spy = LogitSpy(eng) if not isinstance(eng._jstep_ragged, LogitSpy) \
        else eng._jstep_ragged
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_new_tokens=new_tokens))
    gen = {}
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                assert out.finish_reason == "length", out.finish_reason
                gen[out.request_id] = list(out.generated)
                eng.release_request(out.request_id)
        eng.block_manager.check_invariants()
    return gen, spy, eng


def assert_against_reference(model, prompts, gen, spy, tol=2e-4):
    """Every row that could yield a token (a prompt's last row, every
    decode row) equals the reference's logits at that position of the
    request's own history."""
    checked = 0
    for rid, p in prompts.items():
        hist = list(p) + gen[rid]
        want = ref_logits(model, hist)
        for n in range(len(p), len(hist)):
            np.testing.assert_allclose(spy.logits[(rid, n)], want[n - 1],
                                       atol=tol, rtol=0, err_msg=f"{rid}@{n}")
            assert gen[rid][n - len(p)] == int(np.argmax(want[n - 1]))
            checked += 1
    return checked


def test_engine_chunked_prefill_decode_and_slot_reuse(model):
    """Five requests through three slots, prompts over the 16-token
    budget: chunk-to-chunk state, decode through the cache, slots and
    blocks reused, no prefix cache."""
    prompts = prompts_with([], [30, 5, 41, 17, 9])
    gen, spy, eng = serve(model, prompts)
    assert eng.cfg.prefix_cache is False and eng._snaps is None
    assert assert_against_reference(model, prompts, gen, spy) == 25
    assert len(eng._seen_shapes) == 1
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0
    assert snap["state_snapshots_in_use"] == 0


def test_prefix_reuse_over_snapshots_matches_reference(model):
    """One request warms a 33-token prefix; five more share it. Each is
    admitted on the deepest snapshot (32 tokens), its slot loaded from it,
    and every logit still equals the reference's full forward from
    position 0."""
    prefix = [int(t) for t in
              np.random.default_rng(5).integers(1, 160, 33)]
    prompts = prompts_with(prefix, [3, 5, 11, 17, 9, 1])
    first = {"r0": prompts.pop("r0")}
    gen, spy, eng = serve(model, first, prefix_cache=True)
    more, _, _ = serve(model, prompts, engine=eng)
    gen.update(more)
    prompts.update(first)
    assert assert_against_reference(model, prompts, gen, spy) == 30
    bm = eng.block_manager
    assert bm.num_snapshot_hits == 5 and bm.num_prefix_hit_tokens == 160
    assert bm.num_cow_copies == 0
    assert sum(spy.restored) == 5
    assert len(eng._seen_shapes) == 1
    snap = eng.metrics.snapshot()
    assert snap["state_snapshot_hits"] == 5
    assert snap["state_snapshots_in_use"] > 0
    assert snap["state_slots_in_use"] == 0


def test_restored_request_is_bitwise_the_request_from_zero(model):
    """The same prompt served twice by one engine: from zero state (its
    chunks end on block boundaries, snapshots are taken) and then from the
    deepest snapshot. Same rows at the same stream positions through the
    same program: the logits are equal bit for bit (float32, CPU)."""
    prompt = [int(t) for t in np.random.default_rng(7).integers(1, 160, 38)]
    g0, spy, eng = serve(model, {"zero": prompt}, prefix_cache=True)
    g1, _, _ = serve(model, {"again": prompt}, engine=eng)
    assert eng.block_manager.num_snapshot_hits == 1
    assert eng.block_manager.last_hit_tokens == 32
    assert g0["zero"] == g1["again"]
    for n in range(len(prompt), len(prompt) + 5):
        a, b = spy.logits[("zero", n)], spy.logits[("again", n)]
        assert np.array_equal(a, b), n
    # and a third engine, prefix cache off, says the same
    g2, spy2, _ = serve(model, {"plain": prompt})
    assert g2["plain"] == g0["zero"]
    np.testing.assert_allclose(spy2.logits[("plain", len(prompt))],
                               spy.logits[("again", len(prompt))],
                               atol=1e-5, rtol=0)


def test_preempted_request_recomputes_from_its_deepest_snapshot(model):
    """Recompute preemption under a pool too small for three long
    requests: the victim comes back on a snapshot instead of from zero
    state, and every stream is still the reference's."""
    prefix = [int(t) for t in
              np.random.default_rng(9).integers(1, 160, 24)]
    prompts = prompts_with(prefix, [9, 14, 6], seed=4)
    gen, spy, eng = serve(model, prompts, new_tokens=14, prefix_cache=True,
                          num_blocks=26)
    assert eng.scheduler.num_preemptions > 0
    assert eng.block_manager.num_snapshot_hits > 0
    for rid, p in prompts.items():
        hist = list(p) + gen[rid]
        want = ref_logits(model, hist)
        assert gen[rid] == [int(np.argmax(want[n - 1]))
                            for n in range(len(p), len(hist))]


def test_spans_and_counters_of_the_snapshot_path(model):
    from paddle_tpu import profiler

    prefix = [int(t) for t in
              np.random.default_rng(11).integers(1, 160, 20)]
    prompts = prompts_with(prefix, [4, 7], seed=6)
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as prof:
        _, _, eng = serve(model, {"r0": prompts["r0"]}, prefix_cache=True)
        serve(model, {"r1": prompts["r1"]}, engine=eng)
    events = prof._events if hasattr(prof, "_events") else None
    rec = [e for e in profiler._recorder.events] if events is None \
        else events
    disp = [e["args"] for e in rec if e["name"] == "engine.dispatch"]
    sched = [e["args"] for e in rec if e["name"] == "engine.schedule"
             and "prompt_tokens" in e["args"]]
    assert sum(a["snapshots_restored"] for a in disp) == 1
    assert sum(a["snapshots_taken"] for a in disp) >= 2
    assert all("state_slots" in a for a in disp)
    assert [a["prompt_tokens"] for a in sched] == [24, 27]
    # the trie matches the prefix's 5 blocks; the deepest snapshot
    # stands at 16 (chunks of 16 and 8), so one block is recomputed
    assert [a["prefix_hit_tokens"] for a in sched] == [0, 16]
    assert [a["prefix_recomputed_tokens"] for a in sched] == [0, 4]


# -- the block manager's snapshot pool ---------------------------------------------
def manager(**kw):
    args = dict(num_blocks=32, block_size=BS, enable_prefix_cache=True,
                state_slots=4, state_snapshots=3)
    args.update(kw)
    return BlockManager(args.pop("num_blocks"), args.pop("block_size"),
                        **args)


def prefill(bm, rid, tokens, chunks):
    """Walk a request through ``chunks`` (token counts after each step):
    allocate, plan, 'run', commit. Returns the hit of the admission."""
    bm.allocate(rid, chunks[0], tokens=tokens)
    hit = bm.last_hit_tokens
    for covered in chunks:
        if covered != chunks[0]:
            bm.append_slot(rid, covered, write_from=prev)
        bm.plan_snapshots([(rid, covered, len(tokens))])
        bm.take_state_copies()
        bm.commit_prefix(rid, tokens, covered)
        prev = covered
        bm.check_invariants()
    return hit


def test_hit_is_cut_back_to_the_deepest_snapshot():
    bm = manager()
    a = list(range(100, 122))                      # 22 tokens, 5 full blocks
    assert prefill(bm, "a", a, [8, 18, 22]) == 0   # snapshot at 8 only
    assert bm.state_snapshots_in_use == 1
    assert bm._matched_chain(a + [1, 2], 24)[0] == bm.block_table("a")[:5]
    assert bm.match_prefix(a + [1, 2]) == 8        # trie says 20, state says 8
    table_a = bm.block_table("a")
    bm.allocate("b", 16, tokens=a + [1, 2])
    assert bm.last_hit_tokens == 8
    assert bm.num_prefix_recomputed_tokens == 12
    table_b = bm.block_table("b")
    assert table_b[:2] == table_a[:2]              # shared up to the snapshot
    assert not set(table_b[2:]) & set(table_a)     # the rest claimed fresh
    restores, captures = bm.take_state_copies()
    assert restores == [(bm._snap_index[bm._key_hash[
        bm._block_key[table_a[1]]]], bm.state_slot("b"))]
    assert captures == []
    bm.check_invariants()


def test_no_shared_block_is_ever_written():
    bm = manager()
    a = list(range(100, 117))
    prefill(bm, "a", a, [8, 16, 17])               # snapshots at 8 and 16
    for rid, extra in (("b", [1]), ("c", [1, 2, 3, 4, 5])):
        toks = a[:16] + extra
        hit = bm.match_prefix(toks)
        assert hit == 16
        bm.allocate(rid, len(toks), tokens=toks)
        # every block at or past the first written position is private
        table = bm.block_table(rid)
        assert all(bm.ref_count(b) == 1 for b in table[hit // BS:])
        assert all(bm.ref_count(b) >= 2 for b in table[:hit // BS])
        bm.take_state_copies()
    assert bm.num_cow_copies == 0 and bm.take_cow_pairs() == []
    # an identical prompt: the hit stays below its last token
    assert bm.match_prefix(a[:16]) == 8
    bm.check_invariants()


def test_a_chain_without_snapshots_is_a_miss_from_zero_state():
    bm = manager()
    a = list(range(100, 113))
    prefill(bm, "a", a, [13])                      # one chunk, no boundary
    assert bm.state_snapshots_in_use == 0
    assert bm.match_prefix(a + [5]) == 0
    bm.allocate("b", 14, tokens=a + [5])
    assert bm.last_hit_tokens == 0 and bm.take_state_copies() == ([], [])
    assert bm.num_prefix_recomputed_tokens == 12
    bm.check_invariants()


def test_eviction_takes_the_snapshot_with_its_block():
    bm = manager(num_blocks=6)
    a = list(range(100, 112))
    prefill(bm, "a", a, [8, 12])                   # snapshots at 8 and 12
    assert bm.state_snapshots_in_use == 2
    bm.free("a")                                   # cached-free, still keyed
    assert bm.match_prefix(a + [1]) == 12
    bm.check_invariants()
    # a stranger needs every block: the chain's blocks are reclaimed
    # oldest first and their snapshots go with them
    bm.allocate("x", 24, tokens=list(range(24)))
    bm.take_state_copies()
    assert bm.state_snapshots_in_use == 0
    assert bm.num_snapshot_evictions == 2
    assert bm.match_prefix(a + [1]) == 0
    bm.check_invariants()


def test_least_recently_hit_snapshot_goes_first_but_never_a_pending_load():
    bm = manager(state_snapshots=2, state_slots=4)
    a, b = list(range(100, 109)), list(range(200, 209))
    prefill(bm, "a", a, [8, 9])
    prefill(bm, "b", b, [8, 9])
    assert bm.state_snapshots_in_use == 2
    # "a" is hit again (most recent), then a third chain needs an entry
    bm.allocate("a2", 9, tokens=a[:8] + [1])
    pending = bm._restores[0][0]
    c = list(range(300, 309))
    bm.allocate("c", 8, tokens=c)
    bm.plan_snapshots([("c", 8, 9)])
    restores, captures = bm.take_state_copies()
    assert [e for e, _ in restores] == [pending]
    assert captures and captures[0][1] != pending  # took "b"'s, not "a"'s
    bm.commit_prefix("c", c, 8)
    assert bm.match_prefix(b + [1]) == 0 and bm.match_prefix(a + [1]) == 8
    assert bm.num_snapshot_evictions == 1
    bm.check_invariants()
    # a request freed between plan and commit gives its entry back
    bm.append_slot("a2", 12, write_from=9)
    bm.plan_snapshots([("a2", 12, 20)])
    bm.free("a2")
    assert bm.take_state_copies() == ([], [])
    bm.check_invariants()


def test_a_never_hit_snapshot_goes_before_one_that_was_hit():
    bm = manager(state_snapshots=2)
    a, b, c = (list(range(k, k + 9)) for k in (100, 200, 300))
    prefill(bm, "a", a, [8, 9])
    bm.allocate("a2", 9, tokens=a[:8] + [1])       # "a"'s snapshot is hit
    bm.take_state_copies()
    prefill(bm, "b", b, [8, 9])                    # newer, never hit
    prefill(bm, "c", c, [8, 9])                    # takes "b"'s entry
    assert [bm.match_prefix(t + [1]) for t in (a, b, c)] == [8, 0, 8]
    bm.check_invariants()


def test_snapshots_need_state_slots_and_the_trie():
    with pytest.raises(ValueError, match="state_snapshots"):
        BlockManager(8, BS, state_snapshots=2)
    with pytest.raises(ValueError, match="recurrent state"):
        BlockManager(8, BS, enable_prefix_cache=True, state_slots=2)
    with pytest.raises(ValueError, match="recurrent state"):
        BlockManager(8, BS, enable_prefix_cache=True, state_slots=2,
                     state_snapshots=2, window_blocks=4, window=8)


# -- the scheduler's chunk cut -------------------------------------------------------
def _schedule_sizes(bm, prompt_len, budget=10, rounds=4):
    sched = Scheduler(bm, SchedulerConfig(max_num_seqs=2,
                                          max_batched_tokens=budget))
    req = Request("r", list(range(1, prompt_len + 1)),
                  SamplingParams(max_new_tokens=2))
    sched.add(req)
    sizes = []
    for _ in range(rounds):
        batch = sched.schedule()
        if batch.is_empty:
            break
        sizes.append(batch.num_scheduled[0])
        req.num_cached += batch.num_scheduled[0]
        bm.plan_snapshots([("r", req.num_cached, prompt_len)])
        bm.take_state_copies()
        bm.commit_prefix("r", req.prompt_ids, req.num_cached)
        if req.num_cached >= prompt_len:
            break
    return sizes


def test_chunk_cut_touches_only_state_under_the_prefix_cache():
    # a plain model (prefix cache on, no state): budget-sized chunks
    assert _schedule_sizes(BlockManager(32, BS, enable_prefix_cache=True),
                           25) == [10, 10, 5]
    # state slots without the prefix cache: the same
    assert _schedule_sizes(BlockManager(32, BS, state_slots=2),
                           25) == [10, 10, 5]
    # state under the prefix cache: chunks end on block boundaries until
    # the one that reaches the prompt's end
    assert _schedule_sizes(manager(), 25) == [8, 8, 9]
    # a budget below one block cannot be cut: it runs as it is
    assert _schedule_sizes(manager(), 7, budget=3) == [3, 1, 3]


# -- what the engine takes and refuses ---------------------------------------------
def _refusal(kinds, **cfg):
    eng = object.__new__(LLMEngine)
    eng.model = object()
    eng.cfg = EngineConfig(**cfg)
    eng._cache_spec = {"layers": [{"kind": k} for k in kinds]}
    eng._spec_kinds = frozenset(kinds)
    eng._refuse_for_cache_spec()


@pytest.mark.parametrize("kinds, words", [
    (("window", "full"), "a block released behind the window cannot be "
                         "shared"),
    (("latent",), "the latent pool has no block-copy path yet"),
    (("latent_indexed",), "the latent entries AND their index keys"),
    (("latent_window",), "a latent block released behind the window "
                         "cannot be shared"),
    (("state", "window", "reads"), "does not carry the recurrent state at "
                                   "its boundary"),
    (("full", "reads"), "reads another layer's pages"),
])
def test_prefix_cache_stays_refused_for_the_other_kinds(kinds, words):
    with pytest.raises(ValueError, match="prefix_cache=True") as e:
        _refusal(kinds, prefix_cache=True)
    assert words in str(e.value) and "cache_spec" in str(e.value)


def test_prefix_cache_is_taken_for_state_full_and_none(model):
    _refusal(("state", "full", "none"), prefix_cache=True)
    # off unless asked for by name; the other knobs stay refused
    assert LLMEngine(model, EngineConfig(
        block_size=BS, max_num_seqs=2, max_model_len=32,
        max_batched_tokens=8)).cfg.prefix_cache is False
    eng = LLMEngine(model, EngineConfig(
        block_size=BS, max_num_seqs=2, max_model_len=32,
        max_batched_tokens=8, prefix_cache=True))
    assert eng.cfg.num_state_snapshots == 4        # two a sequence slot
    assert eng.block_manager.state_snapshots == 4
    assert len(eng._snaps) == 4 and eng._snaps[0]["ssm"].shape[0] == 4
    for knob in (dict(swap_mode="host"), dict(tp_degree=2),
                 dict(kv_tiers={"num_host_blocks": 8}, prefix_cache=True)):
        with pytest.raises(ValueError, match="cache_spec"):
            LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                          max_model_len=32,
                                          max_batched_tokens=8, **knob))
