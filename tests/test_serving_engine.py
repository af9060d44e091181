"""LLMEngine end-to-end on XLA:CPU (tiny Llama, GQA config).

Pins the PR's acceptance criteria: >= 8 concurrent requests of unequal
lengths served to completion with continuous batching (a late arrival
joins the running batch), paged greedy decode token-identical to the
naive full-recompute ``generate``, and preemption-on-OOM reclaiming
blocks while still completing every request."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    EngineConfig, LLMEngine, SamplingParams,
)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()          # 4 heads / 2 KV heads: GQA path
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _naive(model, prompt, max_new):
    ids = paddle.to_tensor(np.asarray([prompt], np.int32))
    out = model.generate(ids, max_new_tokens=max_new, use_cache=False)
    return [int(t) for t in out.numpy()[0][len(prompt):]]


def _prompts(rng, vocab, lens):
    return [list(map(int, rng.integers(0, vocab, size=n))) for n in lens]


def _ragged_step(m, rows, kcs, vcs, T=16, S=2, max_blocks=4):
    """One ``forward_ragged`` call the way the engine packs it: ``rows``
    is ``[(new_tokens, num_cached, block_table)]``; the stream and the
    slots are padded to the fixed (T, S). Returns each row's logits and
    the caches."""
    ids = np.zeros((T,), np.int32)
    cu = np.zeros((S + 1,), np.int32)
    ctx = np.zeros((S,), np.int32)
    bt = np.full((S, max_blocks), -1, np.int32)
    off = 0
    for i, (toks, cached, table) in enumerate(rows):
        ids[off:off + len(toks)] = toks
        off += len(toks)
        cu[i + 1] = off
        ctx[i] = cached + len(toks)
        bt[i, :len(table)] = table
    cu[len(rows) + 1:] = off
    logits, kcs, vcs = m.forward_ragged(ids, kcs, vcs, bt, cu, ctx,
                                        np.int32(len(rows)))
    return logits.numpy()[:len(rows)], kcs, vcs


def _dense_last(m, tokens):
    return m(paddle.to_tensor(np.asarray([tokens], np.int32))).numpy()[0, -1]


def _whole_prompt(m, a, b, step):
    (la,), kcs, _ = step([(a, 0, [0, 1, 2])])
    # prefill wrote the cache: the first layer's block 0 is nonzero
    assert float(np.abs(np.asarray(kcs[0])[0]).sum()) > 0
    return [(la, a)]


def _two_chunks(m, a, b, step):
    step([(a[:4], 0, [0, 1, 2])])         # a mid-prompt row: no sample
    (la,), _, _ = step([(a[4:], 4, [0, 1, 2])])
    return [(la, a)]


def _continuation_beside_decode(m, a, b, step):
    (_, lb), _, _ = step([(a[:5], 0, [0, 1, 2]), (b, 0, [3, 4])])
    nxt = int(np.argmax(lb))
    # decode rows go first, as the scheduler orders them
    (lb2, la), _, _ = step([([nxt], len(b), [3, 4]),
                            (a[5:], 5, [0, 1, 2])])
    return [(lb, b), (lb2, b + [nxt]), (la, a)]


@pytest.mark.parametrize("case", [_whole_prompt, _two_chunks,
                                  _continuation_beside_decode],
                         ids=lambda f: f.__name__.strip("_"))
def test_prefill_logits_match_naive_forward(tiny_model, case):
    """The compiled serving step's forward == the dense causal forward's
    last-token logits, for a prompt prefilled whole, one fed as two
    chunks through the paged cache, and a chunk continuation sharing a
    step with a decode row."""
    m = tiny_model
    cfg = m.config
    rng = np.random.default_rng(0)
    a, b = _prompts(rng, cfg.vocab_size, [9, 6])
    kh = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    # one (NB, BS, KH, D) array per layer, for K and for V
    cache = [(np.zeros((8, 4, kh, hd), np.float32),)
             * cfg.num_hidden_layers] * 2

    def step(rows):
        logits, cache[0], cache[1] = _ragged_step(m, rows, *cache)
        return logits, cache[0], cache[1]

    for got, tokens in case(m, a, b, step):
        np.testing.assert_allclose(got, _dense_last(m, tokens),
                                   rtol=2e-4, atol=2e-4)


def test_e2e_concurrent_unequal_lengths_with_late_arrival(tiny_model):
    """8 unequal-length requests + 1 late arrival that must join the
    already-running batch; every request finishes, every greedy output
    is token-identical to the naive generate."""
    m = tiny_model
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, m.config.vocab_size,
                       [3, 5, 7, 9, 4, 6, 11, 2])
    late_prompt = _prompts(rng, m.config.vocab_size, [5])[0]
    max_new = 6
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=9,
                                    max_model_len=64))
    sp = SamplingParams(max_new_tokens=max_new)
    rids = [eng.add_request(p, sampling=sp) for p in prompts]

    step_outputs = []
    late_rid = None
    while eng.has_unfinished():
        outs = eng.step()
        step_outputs.append(outs)
        if late_rid is None and eng.metrics.decode_steps >= 2:
            assert eng.scheduler.num_running > 0  # batch is mid-flight
            late_rid = eng.add_request(late_prompt, sampling=sp)
    assert late_rid is not None

    # the late request shared at least one decode iteration with an
    # original request — continuous batching, not drain-and-refill
    early = set(rids)
    shared = [outs for outs in step_outputs
              if any(o.request_id == late_rid for o in outs)
              and any(o.request_id in early for o in outs)]
    assert shared, "late arrival never joined the running batch"

    for rid, p in zip(rids + [late_rid], prompts + [late_prompt]):
        req = eng.get_request(rid)
        assert req.is_finished and req.num_generated == max_new
        assert req.generated == _naive(m, p, max_new), rid
    # all KV blocks reclaimed at completion
    assert eng.block_manager.num_free_blocks == eng.cfg.num_blocks
    eng.block_manager.check_invariants()


def test_preemption_on_oom_reclaims_blocks_and_completes(tiny_model):
    """Cache sized so the batch cannot all reach full length: the engine
    must preempt (reclaiming blocks), re-admit, and still produce
    token-identical greedy output for EVERY request."""
    m = tiny_model
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, m.config.vocab_size, [6, 8, 5, 7])
    max_new = 8
    # 10 blocks * 4 slots = 40 token slots < 4 requests * up to 16 tokens
    eng = LLMEngine(m, EngineConfig(block_size=4, num_blocks=10,
                                    max_num_seqs=4, max_model_len=32))
    sp = SamplingParams(max_new_tokens=max_new)
    rids = [eng.add_request(p, sampling=sp) for p in prompts]
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 500, "engine failed to converge"
        eng.block_manager.check_invariants()
    assert eng.scheduler.num_preemptions > 0, \
        "test config was supposed to force preemption"
    for rid, p in zip(rids, prompts):
        assert eng.get_request(rid).generated == _naive(m, p, max_new)
    assert eng.block_manager.num_free_blocks == eng.cfg.num_blocks


def test_generate_default_uses_paged_path_and_matches_naive(tiny_model):
    m = tiny_model
    rng = np.random.default_rng(3)
    ids = rng.integers(0, m.config.vocab_size, size=(2, 7)).astype(
        np.int32)
    x = paddle.to_tensor(ids)
    out_paged = m.generate(x, max_new_tokens=5)           # default: paged
    assert getattr(m, "_serving_engine", None) is not None
    out_naive = m.generate(x, max_new_tokens=5, use_cache=False)
    np.testing.assert_array_equal(out_paged.numpy(), out_naive.numpy())
    # engine is cached and reused across calls
    eng = m._serving_engine
    out2 = m.generate(x, max_new_tokens=5)
    assert m._serving_engine is eng
    np.testing.assert_array_equal(out2.numpy(), out_paged.numpy())


def test_streaming_callback_order_and_eos(tiny_model):
    m = tiny_model
    rng = np.random.default_rng(4)
    p = list(map(int, rng.integers(0, m.config.vocab_size, size=5)))
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=64))
    # find the greedy continuation, then replay with its 2nd token as EOS
    first = eng.generate([p], SamplingParams(max_new_tokens=4))[0]
    events = []
    rid = eng.add_request(
        p, sampling=SamplingParams(max_new_tokens=4,
                                   eos_token_id=first[1]),
        callback=lambda r, tok, done: events.append((r, tok, done)))
    eng.run()
    req = eng.get_request(rid)
    assert req.is_finished
    assert [t for _, t, _ in events] == first[:2]  # stopped AT the EOS
    assert [d for _, _, d in events] == [False, True]
    assert all(r == rid for r, _, _ in events)


def test_serving_counters_registered_in_profiler(tiny_model):
    from paddle_tpu import profiler

    m = tiny_model
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    eng.add_request([1, 2, 3], sampling=SamplingParams(max_new_tokens=2))
    c = profiler.counters()
    mine = {k: v for k, v in c.items()
            if k.startswith("serving/") and k.endswith(f"#{id(eng)}")}
    assert mine[f"serving/queue_depth#{id(eng)}"] == 1
    assert mine[f"serving/kv_block_utilization#{id(eng)}"] == 0.0
    eng.run()
    c = profiler.counters()
    assert c[f"serving/num_waiting#{id(eng)}"] == 0
    assert c[f"serving/tokens_per_sec#{id(eng)}"] > 0
    snap = eng.metrics.snapshot()
    assert snap["num_finished"] == 1
    assert snap["ttft_ms_avg"] > 0


def test_cow_copies_surfaced_by_metrics(tiny_model):
    """BlockManager.num_cow_copies was bumped since PR 13 but surfaced
    by no gauge or snapshot key — the counter-snapshot-drift class."""
    from paddle_tpu import profiler
    from paddle_tpu.serving.metrics import ServingMetrics

    assert "cow_copies" in ServingMetrics.GAUGES
    m = tiny_model
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    eng.add_request([1, 2, 3], sampling=SamplingParams(max_new_tokens=2))
    eng.run()
    c = profiler.counters()
    assert c[f"serving/cow_copies#{id(eng)}"] == \
        eng.block_manager.num_cow_copies
    assert eng.metrics.snapshot()["serving_cow_copies"] == \
        eng.block_manager.num_cow_copies


def test_engine_admission_validation(tiny_model):
    m = tiny_model
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=16))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.add_request(list(range(1, 15)),
                        sampling=SamplingParams(max_new_tokens=8))
    eng.add_request("dup", [1, 2], SamplingParams(max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        eng.add_request("dup", [3, 4], SamplingParams(max_new_tokens=2))


def test_sampled_decode_is_reproducible_per_request(tiny_model):
    """temperature>0 through the engine: per-request RNG streams make
    the same (seed, prompt) reproduce the same tokens."""
    m = tiny_model
    rng = np.random.default_rng(5)
    p = list(map(int, rng.integers(0, m.config.vocab_size, size=4)))
    sp = SamplingParams(max_new_tokens=5, temperature=0.8, top_p=0.9,
                        seed=123)
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    a = eng.generate([p], sp)[0]
    b = eng.generate([p], sp)[0]
    assert a == b
    assert all(0 <= t < m.config.vocab_size for t in a)


def test_greedy_decode_never_fetches_full_logits(tiny_model):
    """Fully in-graph sampling (ISSUE 11): greedy AND sampled workloads
    ship one packed int row per slot each step and NEVER pull the
    B×vocab logits to host — ``num_logits_fetches`` stays 0 for both."""
    m = tiny_model
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, m.config.vocab_size, [4, 6])
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=4))
    assert eng.num_logits_fetches == 0
    assert all(len(o) == 4 for o in outs)
    # sampled decode used to flip to a B×vocab fetch; the in-graph
    # sampler keeps the boundary at B ints
    eng.generate([prompts[0]],
                 SamplingParams(max_new_tokens=3, temperature=0.7,
                                seed=1))
    assert eng.num_logits_fetches == 0
    assert eng.num_sampled_steps > 0


def test_mixed_greedy_and_sampled_batch_parity(tiny_model):
    """A batch mixing greedy and sampled requests runs ONE in-graph
    sampling path (greedy rows one-hot), the greedy request's tokens
    still match the naive generate exactly, and no step fetches
    logits."""
    m = tiny_model
    rng = np.random.default_rng(7)
    pg, ps = _prompts(rng, m.config.vocab_size, [5, 5])
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    rg = eng.add_request(pg, sampling=SamplingParams(max_new_tokens=4))
    rs = eng.add_request(
        ps, sampling=SamplingParams(max_new_tokens=4, temperature=0.8,
                                    seed=9))
    eng.run()
    assert eng.get_request(rg).generated == _naive(m, pg, 4)
    assert len(eng.get_request(rs).generated) == 4
    assert eng.num_logits_fetches == 0
