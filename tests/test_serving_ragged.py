"""The serving step: parity + compile-count pins.

The engine (single-shape packed step + chunked prefill + COW prefix
caching) must be TOKEN-IDENTICAL to oracles that share none of it —
greedy rows to the dense full-recompute forward, sampled rows to that
forward's logits put through the sampler with the request's own key —
and a sampled stream must not depend on chunking, batch mix, preemption
or fleet hand-off, while the engine compiles exactly ONE step function
for a whole mixed prefill/decode workload."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.watchdog import PreemptionMonitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    EngineConfig, LLMEngine, Request, SamplingParams,
)
from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _naive(model, prompt, max_new):
    ids = paddle.to_tensor(np.asarray([prompt], np.int32))
    out = model.generate(ids, max_new_tokens=max_new, use_cache=False)
    return [int(t) for t in out.numpy()[0][len(prompt):]]


def _prompts(seed, vocab, lens):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, vocab, size=n))) for n in lens]


def _cfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 64)
    return EngineConfig(**kw)


def _serve(model, prompts, samplings, **cfg_kw):
    eng = LLMEngine(model, _cfg(**cfg_kw))
    rids = [eng.add_request(f"r{i}", p, sampling=sp)
            for i, (p, sp) in enumerate(zip(prompts, samplings))]
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 500, "engine failed to converge"
    return eng, [eng.get_request(r).generated for r in rids]


def _alone(model, rid, prompt, sampling):
    """The same request served alone by a fresh engine whose budget takes
    the prompt whole: what a sampled row's stream must equal, since it is
    a function of the request's key and emitted-step count only."""
    eng = LLMEngine(model, _cfg())
    eng.add_request(rid, prompt, sampling=sampling)
    eng.run()
    assert eng.scheduler.num_prefill_chunks == 0
    assert eng.scheduler.num_preemptions == 0
    return eng.get_request(rid).generated


# ---------------------------------------------------------------------------
# config resolution
# ---------------------------------------------------------------------------
def test_ragged_is_the_default_for_ragged_capable_models(tiny_model):
    """No option picks a step: the defaults are the chunking scheduler on
    the step's raw budget and the prefix cache, and a model that cannot
    run that step is refused by name."""
    ecfg = EngineConfig(block_size=4, max_num_seqs=2, max_model_len=32)
    eng = LLMEngine(tiny_model, ecfg)
    assert eng.cfg.prefix_cache
    assert eng.scheduler.config.max_batched_tokens == eng._ragged_T == 64

    class DenseOnly:
        config = tiny_model.config

    with pytest.raises(ValueError, match="DenseOnly has no forward_ragged"):
        LLMEngine(DenseOnly(), ecfg)


# ---------------------------------------------------------------------------
# parity + compile count
# ---------------------------------------------------------------------------
def test_mixed_workload_parity_and_single_compiled_shape(tiny_model):
    """Long prompts over the token budget (forced chunks), short
    prompts, two sampled rows (one of them chunked): the greedy rows ==
    naive generate, the sampled rows == the same requests served alone
    and whole, and the WHOLE run (chunked prefills, mixed batches,
    shrinking decode tails) dispatched ONE compiled step shape."""
    m = tiny_model
    prompts = _prompts(21, m.config.vocab_size, [29, 3, 22, 6])
    sps = [SamplingParams(max_new_tokens=6),
           SamplingParams(max_new_tokens=5, temperature=0.8, seed=3),
           SamplingParams(max_new_tokens=6, temperature=0.9, top_k=12,
                          top_p=0.95, seed=5),
           SamplingParams(max_new_tokens=4)]
    # budget 16 < the 29/22-token prompts: the engine must chunk
    eng, outs = _serve(m, prompts, sps, max_batched_tokens=16)
    for i in (0, 3):             # greedy rows vs the full-recompute oracle
        assert outs[i] == _naive(m, prompts[i], sps[i].max_new_tokens)
    assert eng.get_request("r2").was_chunked
    for i in (1, 2):             # sampled rows: chunking and mix invariant
        assert outs[i] == _alone(m, f"r{i}", prompts[i], sps[i])
    assert len(eng._seen_shapes) == 1, eng._seen_shapes
    snap = eng.metrics.snapshot()
    assert snap["serving_prefill_chunks"] > 0
    assert snap["mixed_steps"] > 0, \
        "chunk continuations never shared a step with decode rows"
    assert eng.metrics.num_generated_tokens == sum(map(len, outs))


def test_sampled_stream_matches_dense_forward_through_the_sampler(
        tiny_model):
    """The oracle that shares neither paging nor scheduling with the
    engine: each token of a sampled request is the draw that
    ``sample_or_verify`` makes from the dense float32 forward's last-row
    logits over the request's history, with the request's own key,
    advanced as the sampler advances it. The prompt is chunked and
    shares its steps with another request."""
    from paddle_tpu.ops.sampling import sample_or_verify

    m = tiny_model
    prompt, other = _prompts(25, m.config.vocab_size, [21, 7])
    sp = SamplingParams(max_new_tokens=8, temperature=0.9, top_k=20,
                        top_p=0.9, seed=17)
    eng, (got, _) = _serve(
        m, [prompt, other], [sp, SamplingParams(max_new_tokens=8)],
        max_batched_tokens=8)
    assert eng.get_request("r0").was_chunked

    key = Request(request_id="r0", prompt_ids=prompt,
                  sampling=sp).device_key[None]
    history = list(prompt)
    for step, tok in enumerate(got):
        logits = m(paddle.to_tensor(np.asarray([history], np.int32)))
        toks, n_emit, key = sample_or_verify(
            logits._data[:, -1:].astype(jnp.float32),
            jnp.zeros((1, 0), jnp.int32), jnp.zeros((1,), jnp.int32), key,
            jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            jnp.asarray([sp.top_p], jnp.float32))
        assert int(n_emit[0]) == 1
        assert int(toks[0, 0]) == tok, f"token {step} of {got}"
        history.append(tok)
    np.testing.assert_array_equal(
        np.asarray(key[0]), eng.get_request("r0").device_key)


def test_parity_through_preemption(tiny_model):
    """Cache sized so the batch cannot all reach full length: the engine
    preempts (the sampled row among the victims) and recomputes, and
    every stream is what it would have been undisturbed."""
    m = tiny_model
    prompts = _prompts(22, m.config.vocab_size, [6, 8, 5, 7])
    sps = [SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8, temperature=0.7, seed=11)]
    eng, outs = _serve(m, prompts, sps, num_blocks=10, max_model_len=32)
    assert eng.scheduler.num_preemptions > 0
    assert eng.get_request("r3").num_preemptions > 0
    for i in (0, 1, 2):
        assert outs[i] == _naive(m, prompts[i], 8)
    assert outs[3] == _alone(m, "r3", prompts[3], sps[3])
    assert eng.block_manager.num_free_blocks == eng.cfg.num_blocks
    eng.block_manager.check_invariants()


def test_prefix_cache_hit_cap_and_cow_keep_parity(tiny_model):
    """Re-sent identical prompts hit the full-prompt cache, which is
    capped at total-1 so one token is always computed; the capped write
    lands in a shared block -> COW. Outputs must equal the cold run's
    exactly, and the pool must return to full."""
    m = tiny_model
    # length 12 = exactly 3 full blocks: the whole prompt is cacheable,
    # so the hit is capped and the capped write lands in a SHARED full
    # block (a 13-token prompt would put it in a fresh partial block
    # and never exercise COW)
    prompt = _prompts(23, m.config.vocab_size, [12])[0]
    sp = SamplingParams(max_new_tokens=6)
    eng = LLMEngine(m, _cfg())
    waves = []
    for wave in range(2):
        # two concurrent identical prompts per wave: wave 2 shares
        # wave 1's committed blocks AND the pair shares within the wave
        rids = [eng.add_request(f"w{wave}-{i}", list(prompt), sampling=sp)
                for i in range(2)]
        steps = 0
        while eng.has_unfinished():
            eng.step()
            eng.block_manager.check_invariants()
            steps += 1
            assert steps < 200
        waves.append([eng.get_request(r).generated for r in rids])
    assert waves[0][0] == waves[0][1] == waves[1][0] == waves[1][1]
    assert waves[0][0] == _naive(m, prompt, 6)
    bm = eng.block_manager
    assert bm.num_prefix_hits > 0
    # eff cap: a full 12-token match reports at most 11 cached tokens
    assert 0 < bm.last_hit_tokens < len(prompt)
    assert bm.num_cow_copies > 0, \
        "capped write into a shared block never copy-on-wrote"
    for rid in [f"w{w}-{i}" for w in range(2) for i in range(2)]:
        eng.release_request(rid)
    assert bm.num_free_blocks == eng.cfg.num_blocks
    bm.check_invariants()


def test_fleet_handoff_parity_ragged(tiny_model):
    """Drain one replica of two mid-run: every request finishes with the
    generations of an undisturbed run — greedy rows the dense forward's,
    sampled rows those of the request served alone — so hand-off
    resume-by-recompute and the step compose without disturbing token
    streams."""
    m = tiny_model
    prompts = _prompts(24, m.config.vocab_size, [3, 5, 4, 6, 2, 5])
    sps = [SamplingParams(max_new_tokens=8, temperature=0.8, seed=40 + i)
           if i < 2 else SamplingParams(max_new_tokens=8)
           for i in range(len(prompts))]
    ids = [f"h{i}" for i in range(len(prompts))]
    ref = {rid: (_alone(m, rid, p, sp) if sp.temperature > 0
                 else _naive(m, p, 8))
           for rid, p, sp in zip(ids, prompts, sps)}

    mon = PreemptionMonitor()
    router = FleetRouter([
        InProcessReplica(m, _cfg(drain_grace_s=0.0),
                         replica_id="r0", monitor=mon),
        InProcessReplica(m, _cfg(drain_grace_s=0.0),
                         replica_id="r1")])
    try:
        for rid, p, sp in zip(ids, prompts, sps):
            router.add_request(rid, p, sampling=sp)
        outs = []
        for _ in range(3):
            outs.extend(router.step())
        r0 = router._by_id("r0").engine
        assert r0.scheduler.num_running > 0
        # a sampled request is on the replica about to drain, mid-stream
        assert any(r.sampling.temperature > 0 and r.num_generated > 0
                   for r in r0.scheduler.running)
        mon.request()            # r0 drains -> hand-off to r1
        for _ in range(500):
            if not router.has_unfinished():
                break
            outs.extend(router.step())
    finally:
        mon.uninstall()
    final = {o.request_id: o for o in outs if o.finished}
    assert set(final) == set(ids)
    assert all(final[r].finish_reason in ("stop", "length") for r in ids)
    for rid in ids:
        assert final[rid].generated == ref[rid], rid
    assert router.num_handoffs >= 1


# ---------------------------------------------------------------------------
# the Pallas kernel itself (interpret mode, asked for by name)
# ---------------------------------------------------------------------------
def _ragged_batch(heads, kv_heads, new, ctx_live, t, s, mb, bs, d=16,
                  seed=7, dtype=np.float32, folded=False):
    """One packed step for the op: ``new[i]`` query rows of live slot i
    end a context of ``ctx_live[i]`` tokens; slots past them, rows past
    them and every block-table tail are padding. ``folded``: the caches
    as (blocks, block size, KV heads x d)."""
    n = len(new)
    nb = s * mb
    rng = np.random.RandomState(seed)
    cu = np.zeros(s + 1, np.int32)
    cu[1:n + 1] = np.cumsum(new)
    cu[n + 1:] = cu[n]
    ctx = np.zeros(s, np.int32)
    ctx[:n] = ctx_live
    bt = np.full((s, mb), -1, np.int32)
    free = list(rng.permutation(nb))
    for i, c in enumerate(ctx_live):
        n_blocks = -(-c // bs)
        bt[i, :n_blocks] = [free.pop() for _ in range(n_blocks)]
    cache = (nb, bs, kv_heads * d) if folded else (nb, bs, kv_heads, d)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
        for shape in [(t, heads, d), (t, kv_heads, d), (t, kv_heads, d),
                      cache, cache]
    ) + (bt, cu, ctx, np.int32(n))


# q tiles of 32 rows, one page group holds a slot's whole table: a prefill
# from position 0, a decode row, a mid-context prefill chunk that
# straddles two q tiles, a padding slot and padding rows
_MIXED = dict(new=[13, 1, 25], ctx_live=[13, 9, 33], t=48, s=4, mb=12, bs=4)
# the next three: page groups of 16 pages (128 tokens) under longer tables.
# Decode rows only, over contexts that end exactly on a page boundary, one
# token past it, inside the first page, and at page counts (25, 26, 33)
# that are no multiple of the group
_DECODE = dict(new=[1] * 7, ctx_live=[128, 129, 200, 201, 7, 1, 264],
               t=16, s=8, mb=64, bs=8)
# one chunk over four 128-row q tiles behind a cached prefix of 150 tokens
# (more than one group), a decode row on either side
_LONG_CHUNK = dict(new=[1, 390, 1], ctx_live=[40, 540, 9],
                   t=512, s=4, mb=72, bs=8)
# 2 of 6 slots live, 71 of 384 rows: the last two q tiles hold nothing
_TRAILING_PADDING = dict(new=[70, 1], ctx_live=[131, 17],
                         t=384, s=6, mb=64, bs=8)
# the Jamba call cut down: 20 query heads on ONE folded K/V head of 128,
# blocks of 64 (page groups of 8 pages, 512 tokens), decode rows whose
# contexts end just before, on and just after a group boundary, one over
# three groups, one inside the first page
_MQA = dict(new=[1] * 5, ctx_live=[511, 512, 513, 1100, 40], t=16, s=6,
            mb=20, bs=64, d=128, folded=True, dtype=jnp.bfloat16)
# q tiles of 64 rows: a chunk that straddles the two, then two decode rows
# alone in the second tile beside the chunk's tail
_STRADDLE = dict(new=[40, 30, 1, 1], ctx_live=[40, 95, 17, 300], t=80,
                 s=5, mb=80, bs=4)
# blocks of 16 (groups of 32 pages): slots of 33 pages, no multiple of the
# group, so the second group is one live page and 31 clamped entries
_PAGES_33 = dict(new=[1, 20, 1], ctx_live=[528, 530, 513], t=32, s=4,
                 mb=40, bs=16)
# a 200-key window over a folded cache of two 64-lane heads (the hybrid's
# fold): groups of 128 tokens counted from the page of each slot's oldest
# visible key, which starts mid-page
_WINDOW_FOLDED = dict(new=[1, 30, 1], ctx_live=[350, 301, 60], t=48, s=4,
                      mb=100, bs=4, d=64, folded=True, window=200)


@pytest.mark.parametrize("heads,kv_heads,batch,strict", [
    (4, 4, _MIXED, False), (8, 2, _MIXED, False), (4, 2, _MIXED, False),
    (4, 2, _DECODE, False), (8, 2, _DECODE, False),
    (4, 2, _LONG_CHUNK, False), (4, 4, _LONG_CHUNK, False),
    (4, 2, _TRAILING_PADDING, False), (4, 2, _MIXED, True),
    (4, 2, _DECODE, True),
    # 16-bit caches: a KV head is half of a sublane word (_head_reader)
    (4, 2, dict(_MIXED, dtype=jnp.bfloat16), False),
    (8, 4, dict(_DECODE, dtype=jnp.bfloat16), False),
    # the stream layout: a decode row on its own query heads, page groups
    # of 512 tokens fetched whole (the strict cases count the one wait's
    # bytes, clamped tail pages included)
    (20, 1, _MQA, False), (20, 1, _MQA, True),
    (4, 2, _STRADDLE, False), (4, 2, _STRADDLE, True),
    (8, 2, _PAGES_33, False), (8, 2, _PAGES_33, True),
    (4, 2, _WINDOW_FOLDED, False), (4, 2, _WINDOW_FOLDED, True),
], ids=["mha", "gqa", "rep2", "decode-rep2", "decode-rep4", "chunk-rep2",
        "chunk-mha", "padding-rep2", "strict-mixed", "strict-decode",
        "bf16-mixed", "bf16-decode", "mqa-group-edges",
        "strict-mqa-group-edges", "decode-beside-straddling-chunk",
        "strict-decode-beside-straddling-chunk", "bs16-33-pages",
        "strict-bs16-33-pages", "window-folded", "strict-window-folded"])
def test_ragged_kernel_interpret_matches_ref_on_mixed_batch(
        heads, kv_heads, batch, strict):
    """impl="interpret" vs impl="ref" on one step — the coverage whose
    absence let the kernel rot (it called pl.load/pl.store, which the
    installed Pallas no longer has, and nothing ran it). ``strict`` runs
    the same kernel under the TPU interpreter (``InterpretParams``:
    buffers start as NaN, DMAs and their semaphores are simulated), which
    impl="interpret" (``interpret=True``) is not."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    batch = dict(batch)
    win = {"window": batch.pop("window")} if "window" in batch else {}
    args = _ragged_batch(heads, kv_heads, **batch)
    ref = rpa.ragged_paged_attention(*args, impl="ref", **win)
    if strict:
        q = args[0]
        got = (rpa._ragged_attend_pallas(
            q, ref[1], ref[2], *args[5:], 1.0 / q.shape[-1] ** 0.5,
            pltpu.InterpretParams(), **win),)
    else:
        got = rpa.ragged_paged_attention(*args, impl="interpret", **win)
    tol = 1e-5 if args[0].dtype == np.float32 else 3e-2
    for r, i in zip(ref, got):
        np.testing.assert_allclose(np.asarray(i, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)
    out = np.asarray(got[0], np.float32)
    live = sum(batch["new"])
    assert np.abs(out[:live]).min(axis=(1, 2)).all()  # every live row set
    assert not out[live:].any()                       # padding rows are 0


def test_compiled_kernel_refuses_a_cache_it_cannot_page():
    """One bfloat16 KV head per shard is half a 32-bit sublane word:
    Mosaic cannot slice a page out of such a cache, and the entry point
    says so before the compiler's own message does."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    args = _ragged_batch(4, 1, **dict(_MIXED, dtype=jnp.bfloat16))
    with pytest.raises(NotImplementedError, match="KV head"):
        rpa.ragged_paged_attention(*args, impl="pallas")
    out = rpa.ragged_paged_attention(*args, impl="interpret")[0]
    ref = rpa.ragged_paged_attention(*args, impl="ref")[0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)
