"""Ragged serving hot path (ISSUE 9): parity + compile-count pins.

The ragged engine (single-shape packed step + chunked prefill + COW
prefix caching) must be TOKEN-IDENTICAL to the bucketed engine it
replaces — greedy and sampled, through chunking, preemption and fleet
hand-off — while compiling exactly ONE step function for a whole mixed
prefill/decode workload (the bucket lattice it collapses compiles one
function per (batch, seq) bucket)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.watchdog import PreemptionMonitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
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


def _cfg(ragged, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 64)
    return EngineConfig(ragged=ragged, chunked_prefill=ragged,
                        prefix_cache=ragged, **kw)


def _serve(model, prompts, samplings, ragged, **cfg_kw):
    eng = LLMEngine(model, _cfg(ragged, **cfg_kw))
    rids = [eng.add_request(f"r{i}", p, sampling=sp)
            for i, (p, sp) in enumerate(zip(prompts, samplings))]
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 500, "engine failed to converge"
    return eng, [eng.get_request(r).generated for r in rids]


# ---------------------------------------------------------------------------
# config resolution
# ---------------------------------------------------------------------------
def test_ragged_is_the_default_for_ragged_capable_models(tiny_model):
    eng = LLMEngine(tiny_model, EngineConfig(
        block_size=4, max_num_seqs=2, max_model_len=32))
    assert eng._ragged
    assert eng.cfg.chunked_prefill and eng.cfg.prefix_cache
    # explicit opt-out restores the bucketed lattice wholesale
    eng_b = LLMEngine(tiny_model, EngineConfig(
        block_size=4, max_num_seqs=2, max_model_len=32, ragged=False))
    assert not eng_b._ragged
    assert not eng_b.cfg.chunked_prefill and not eng_b.cfg.prefix_cache


def test_invalid_knob_combinations_raise(tiny_model):
    with pytest.raises(ValueError, match="chunked_prefill"):
        LLMEngine(tiny_model, EngineConfig(
            block_size=4, max_num_seqs=2, max_model_len=32,
            ragged=True, chunked_prefill=False))
    with pytest.raises(ValueError, match="prefix_cache"):
        LLMEngine(tiny_model, EngineConfig(
            block_size=4, max_num_seqs=2, max_model_len=32,
            ragged=False, prefix_cache=True))


# ---------------------------------------------------------------------------
# parity + compile count
# ---------------------------------------------------------------------------
def test_mixed_workload_parity_and_single_compiled_shape(tiny_model):
    """Long prompts over the token budget (forced chunks), short
    prompts, a sampled row: ragged == bucketed for every request, the
    greedy rows == naive generate, and the WHOLE ragged run (chunked
    prefills, mixed batches, shrinking decode tails) dispatched ONE
    compiled step shape while the bucketed run walked its lattice."""
    m = tiny_model
    prompts = _prompts(21, m.config.vocab_size, [29, 3, 22, 6])
    sps = [SamplingParams(max_new_tokens=6),
           SamplingParams(max_new_tokens=5, temperature=0.8, seed=3),
           SamplingParams(max_new_tokens=6),
           SamplingParams(max_new_tokens=4)]
    # budget 16 < the 29/22-token prompts: the ragged engine must chunk
    eng_r, outs_r = _serve(m, prompts, sps, True, max_batched_tokens=16)
    eng_b, outs_b = _serve(m, prompts, sps, False, max_batched_tokens=16)
    assert outs_r == outs_b
    for i in (0, 2, 3):          # greedy rows vs the full-recompute oracle
        assert outs_r[i] == _naive(m, prompts[i], sps[i].max_new_tokens)
    assert len(eng_r._seen_shapes) == 1, eng_r._seen_shapes
    assert len(eng_b._seen_shapes) > 1
    snap = eng_r.metrics.snapshot()
    assert snap["serving_prefill_chunks"] > 0
    assert snap["mixed_steps"] > 0, \
        "chunk continuations never shared a step with decode rows"
    assert snap["padded_token_frac"] == 0.0
    assert eng_r.metrics.num_generated_tokens == \
        eng_b.metrics.num_generated_tokens


def test_parity_through_preemption(tiny_model):
    """Cache sized so the batch cannot all reach full length on either
    engine: both preempt, both still produce identical streams."""
    m = tiny_model
    prompts = _prompts(22, m.config.vocab_size, [6, 8, 5, 7])
    sps = [SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8, temperature=0.7, seed=11),
           SamplingParams(max_new_tokens=8)]
    kw = dict(num_blocks=10, max_model_len=32)
    eng_r, outs_r = _serve(m, prompts, sps, True, **kw)
    eng_b, outs_b = _serve(m, prompts, sps, False, **kw)
    assert eng_r.scheduler.num_preemptions > 0
    assert eng_b.scheduler.num_preemptions > 0
    assert outs_r == outs_b
    for i in (0, 1, 3):
        assert outs_r[i] == _naive(m, prompts[i], 8)
    for eng in (eng_r, eng_b):
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
    eng = LLMEngine(m, _cfg(True))
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
    """Drain one ragged replica of two mid-run: every request finishes
    with generations identical to an uninterrupted BUCKETED single
    engine — hand-off resume-by-recompute and the ragged step compose
    without disturbing token streams."""
    m = tiny_model
    prompts = _prompts(24, m.config.vocab_size, [3, 5, 4, 6, 2, 5])
    sp = SamplingParams(max_new_tokens=8)
    ids = [f"h{i}" for i in range(len(prompts))]
    ref_eng = LLMEngine(m, _cfg(False))
    for rid, p in zip(ids, prompts):
        ref_eng.add_request(rid, p, sampling=sp)
    steps = 0
    while ref_eng.has_unfinished():
        ref_eng.step()
        steps += 1
        assert steps < 500
    ref = {rid: list(ref_eng.get_request(rid).generated) for rid in ids}

    mon = PreemptionMonitor()
    router = FleetRouter([
        InProcessReplica(m, _cfg(True, drain_grace_s=0.0),
                         replica_id="r0", monitor=mon),
        InProcessReplica(m, _cfg(True, drain_grace_s=0.0),
                         replica_id="r1")])
    try:
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        outs = []
        for _ in range(3):
            outs.extend(router.step())
        assert router._by_id("r0").engine.scheduler.num_running > 0
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
                  seed=7, dtype=np.float32):
    """One packed step for the op: ``new[i]`` query rows of live slot i
    end a context of ``ctx_live[i]`` tokens; slots past them, rows past
    them and every block-table tail are padding."""
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
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
        for shape in [(t, heads, d), (t, kv_heads, d), (t, kv_heads, d),
                      (nb, bs, kv_heads, d), (nb, bs, kv_heads, d)]
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


@pytest.mark.parametrize("heads,kv_heads,batch,strict", [
    (4, 4, _MIXED, False), (8, 2, _MIXED, False), (4, 2, _MIXED, False),
    (4, 2, _DECODE, False), (8, 2, _DECODE, False),
    (4, 2, _LONG_CHUNK, False), (4, 4, _LONG_CHUNK, False),
    (4, 2, _TRAILING_PADDING, False), (4, 2, _MIXED, True),
    (4, 2, _DECODE, True),
    # 16-bit caches: a KV head is half of a sublane word (_head_reader)
    (4, 2, dict(_MIXED, dtype=jnp.bfloat16), False),
    (8, 4, dict(_DECODE, dtype=jnp.bfloat16), False),
], ids=["mha", "gqa", "rep2", "decode-rep2", "decode-rep4", "chunk-rep2",
        "chunk-mha", "padding-rep2", "strict-mixed", "strict-decode",
        "bf16-mixed", "bf16-decode"])
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

    args = _ragged_batch(heads, kv_heads, **batch)
    ref = rpa.ragged_paged_attention(*args, impl="ref")
    if strict:
        q = args[0]
        got = (rpa._ragged_attend_pallas(
            q, ref[1], ref[2], *args[5:], 1.0 / q.shape[-1] ** 0.5,
            pltpu.InterpretParams()),)
    else:
        got = rpa.ragged_paged_attention(*args, impl="interpret")
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
