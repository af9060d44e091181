"""In-graph sampling + speculative decoding (the PR's acceptance pins).

Four layers of guarantee, each pinned here:

* device sampler == host oracle: greedy rows are BIT-identical
  (one-hot argmax), sampled rows match the oracle's distribution
  statistically (total-variation bound over a few thousand draws);
* rejection sampling is EXACT: whatever the draft proposes, the
  emitted-token marginal is the target distribution — a greedy target
  therefore makes speculative decode token-identical to the
  non-speculative engine (perfect draft AND garbage draft);
* the hot path never fetches logits: ``num_logits_fetches == 0`` for
  greedy, sampled, and speculative workloads alike;
* edge cases: k=0 is the baseline engine, an all-rejected verify still
  emits the corrected token, EOS inside an accepted draft prefix stops
  exactly there, and a draft/target tokenizer-width mismatch is a
  construction-time ValueError.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def garbage_draft():
    """Same shape, different weights: proposes near-uniformly wrong
    tokens, so verification rejects essentially everything."""
    paddle.seed(777)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _naive(model, prompt, max_new):
    ids = paddle.to_tensor(np.asarray([prompt], np.int32))
    out = model.generate(ids, max_new_tokens=max_new, use_cache=False)
    return [int(t) for t in out.numpy()[0][len(prompt):]]


def _prompts(rng, vocab, lens):
    return [list(map(int, rng.integers(0, vocab, size=n))) for n in lens]


def _run(eng, max_steps=500):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to converge"
    return steps


# -- configuration surface ------------------------------------------------

def test_spec_knobs_are_both_or_neither(tiny_model):
    with pytest.raises(ValueError, match="BOTH"):
        EngineConfig(draft_model=tiny_model)
    with pytest.raises(ValueError, match="BOTH"):
        EngineConfig(num_spec_tokens=2)
    with pytest.raises(ValueError, match=">= 0"):
        EngineConfig(num_spec_tokens=-1)


def test_draft_target_tokenizer_width_mismatch_raises(tiny_model):
    paddle.seed(5)
    narrow = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128))
    narrow.eval()
    with pytest.raises(ValueError, match="tokenizer-width mismatch"):
        LLMEngine(tiny_model, EngineConfig(
            draft_model=narrow, num_spec_tokens=2))


def test_k0_is_the_baseline_engine(tiny_model):
    """num_spec_tokens=0 (the default) builds NO speculative state: no
    proposer, counters stay zero, the step is the plain ragged step."""
    eng = LLMEngine(tiny_model, EngineConfig(block_size=4))
    assert eng._spec is None and eng._spec_R == 1
    eng.add_request([5, 9, 2], sampling=SamplingParams(max_new_tokens=4))
    _run(eng)
    assert eng.num_spec_proposed == 0 and eng.num_spec_accepted == 0
    assert eng.spec_acceptance_rate == 0.0


# -- greedy token identity ------------------------------------------------

def test_spec_greedy_token_identical_perfect_draft(tiny_model):
    """Draft == target: every proposal verifies, so the engine emits
    k+1 tokens per verify step — fewer steps, identical tokens."""
    m = tiny_model
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, m.config.vocab_size, [4, 7, 3, 9])
    max_new = 8
    sp = SamplingParams(max_new_tokens=max_new)

    base = LLMEngine(m, EngineConfig(block_size=4))
    for p in prompts:
        base.add_request(p, sampling=sp)
    base_steps = _run(base)

    eng = LLMEngine(m, EngineConfig(block_size=4, draft_model=m,
                                    num_spec_tokens=3))
    rids = [eng.add_request(p, sampling=sp) for p in prompts]
    spec_steps = _run(eng)

    for rid, p in zip(rids, prompts):
        req = eng.get_request(rid)
        assert req.is_finished and req.generated == _naive(m, p, max_new)
    # a perfect draft verifies (nearly) everything; the whole point is
    # fewer target dispatches for the same tokens
    assert eng.num_spec_proposed > 0
    assert eng.spec_acceptance_rate > 0.9
    assert spec_steps < base_steps
    assert eng.num_logits_fetches == 0


def test_spec_greedy_token_identical_garbage_draft(tiny_model,
                                                   garbage_draft):
    """A bad draft costs acceptance rate, NEVER correctness: rejected
    proposals are replaced by the target's own (greedy) choice, so the
    output stays token-identical to the baseline — the all-rejected
    step degrades to one token per iteration."""
    m = tiny_model
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, m.config.vocab_size, [5, 8, 3])
    max_new = 6
    sp = SamplingParams(max_new_tokens=max_new)
    eng = LLMEngine(m, EngineConfig(block_size=4,
                                    draft_model=garbage_draft,
                                    num_spec_tokens=2))
    rids = [eng.add_request(p, sampling=sp) for p in prompts]
    _run(eng)
    for rid, p in zip(rids, prompts):
        req = eng.get_request(rid)
        assert req.is_finished and req.generated == _naive(m, p, max_new)
    assert eng.num_spec_proposed > 0
    assert eng.num_logits_fetches == 0
    # KV rollback after rejections left the allocator consistent
    assert eng.block_manager.num_free_blocks == eng.cfg.num_blocks
    eng.block_manager.check_invariants()


def test_eos_inside_accepted_draft_prefix(tiny_model):
    """EOS emitted mid-draft must truncate the step's emission exactly
    there (tokens after it in the accepted prefix are discarded)."""
    m = tiny_model
    prompt = _prompts(np.random.default_rng(6), m.config.vocab_size,
                      [6])[0]
    baseline = _naive(m, prompt, 8)
    # pick a mid-run token that FIRST occurs at its position (so the
    # engine can't legitimately stop on an earlier occurrence)
    stop_at = next(i for i in range(2, 7)
                   if baseline[i] not in baseline[:i])
    sp = SamplingParams(max_new_tokens=8, eos_token_id=baseline[stop_at])
    eng = LLMEngine(m, EngineConfig(block_size=4, draft_model=m,
                                    num_spec_tokens=3))
    rid = eng.add_request(prompt, sampling=sp)
    _run(eng)
    req = eng.get_request(rid)
    assert req.finish_reason == "stop"
    # EOS included, nothing after
    assert req.generated == baseline[:stop_at + 1]
    assert eng.block_manager.num_free_blocks == eng.cfg.num_blocks


# -- rejection-sampling kernel (unit level) -------------------------------

def test_all_rejected_verify_emits_corrected_token(tiny_model):
    """Greedy target, every draft token wrong: slot emits EXACTLY one
    token — the target's own argmax at the first verify row."""
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import sample_or_verify

    rng = np.random.default_rng(0)
    s, r, v = 4, 3, 32
    logits = rng.normal(size=(s, r, v)).astype(np.float32)
    am = np.argmax(logits, axis=-1)          # (s, r)
    draft = ((am[:, :r - 1] + 1) % v).astype(np.int32)  # always wrong
    keys = rng.integers(0, 2**32, size=(s, 2), dtype=np.uint32)
    toks, n_emit, nkeys = sample_or_verify(
        jnp.asarray(logits), jnp.asarray(draft),
        jnp.full((s,), r - 1, jnp.int32), jnp.asarray(keys),
        jnp.zeros((s,)), jnp.zeros((s,), jnp.int32), jnp.ones((s,)))
    toks, n_emit = np.asarray(toks), np.asarray(n_emit)
    assert (n_emit == 1).all()
    np.testing.assert_array_equal(toks[:, 0], am[:, 0])
    assert not np.array_equal(np.asarray(nkeys), keys)  # streams moved


def test_fully_accepted_verify_emits_prefix_plus_bonus():
    """Greedy target, draft == argmax everywhere: all k accepted plus
    the bonus token from the last row (n_emit == R)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import sample_or_verify

    rng = np.random.default_rng(1)
    s, r, v = 3, 4, 16
    logits = rng.normal(size=(s, r, v)).astype(np.float32)
    am = np.argmax(logits, axis=-1)
    keys = rng.integers(0, 2**32, size=(s, 2), dtype=np.uint32)
    toks, n_emit, _ = sample_or_verify(
        jnp.asarray(logits), jnp.asarray(am[:, :r - 1].astype(np.int32)),
        jnp.full((s,), r - 1, jnp.int32), jnp.asarray(keys),
        jnp.zeros((s,)), jnp.zeros((s,), jnp.int32), jnp.ones((s,)))
    assert (np.asarray(n_emit) == r).all()
    np.testing.assert_array_equal(np.asarray(toks), am)


# -- distributional parity vs the host oracle -----------------------------

def _oracle_probs(logits, temperature, top_k, top_p):
    """The host sampler's transform (temperature, top-k, then top-p on
    the sorted row), probabilities only (f64)."""
    x = logits.astype(np.float64) / temperature
    x -= x.max()
    p = np.exp(x)
    p /= p.sum()
    if top_k > 0 and top_k < p.size:
        kth = np.partition(p, -top_k)[-top_k]
        p = np.where(p >= kth, p, 0.0)
        p /= p.sum()
    if top_p < 1.0:
        order = np.argsort(-p)
        csum = np.cumsum(p[order])
        keep_n = int(np.searchsorted(csum, top_p) + 1)
        mask = np.zeros_like(p)
        mask[order[:keep_n]] = p[order[:keep_n]]
        p = mask / mask.sum()
    return p


def _tv(counts, p_ref):
    emp = counts / counts.sum()
    return 0.5 * np.abs(emp - p_ref).sum()


def test_filtered_probs_matches_oracle_transform():
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import filtered_probs

    rng = np.random.default_rng(2)
    v = 64
    logits = (rng.normal(size=(3, v)) * 3).astype(np.float32)
    cases = [(0.7, 0, 1.0), (1.3, 10, 1.0), (0.9, 0, 0.8)]
    temps = np.asarray([c[0] for c in cases], np.float32)
    ks = np.asarray([c[1] for c in cases], np.int32)
    ps = np.asarray([c[2] for c in cases], np.float32)
    dev = np.asarray(filtered_probs(jnp.asarray(logits), jnp.asarray(temps),
                                    jnp.asarray(ks), jnp.asarray(ps)))
    for i, (t, k, tp) in enumerate(cases):
        ref = _oracle_probs(logits[i], t, k, tp)
        np.testing.assert_allclose(dev[i], ref, atol=2e-4)


def test_greedy_rows_are_exact_onehot_argmax():
    """Greedy bit-identity: temperature<=0 rows are a {0,1} one-hot at
    np.argmax — not merely argmax-equal after float fuzz."""
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import filtered_probs

    rng = np.random.default_rng(3)
    logits = rng.normal(size=(5, 40)).astype(np.float32)
    logits[2, 7] = logits[2, 31]  # a tie: first occurrence must win
    dev = np.asarray(filtered_probs(
        jnp.asarray(logits), jnp.zeros((5,), jnp.float32),
        jnp.zeros((5,), jnp.int32), jnp.ones((5,), jnp.float32)))
    assert set(np.unique(dev)) <= {0.0, 1.0}
    np.testing.assert_array_equal(np.argmax(dev, -1), np.argmax(logits, -1))


def test_device_draws_match_oracle_distribution():
    """Total variation between N device categorical draws and the host
    oracle's exact distribution stays under the statistical bound."""
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import sample_tokens

    rng = np.random.default_rng(4)
    v, n = 48, 4096
    row = (rng.normal(size=(v,)) * 2).astype(np.float32)
    t, tp = 0.8, 0.9
    p_ref = _oracle_probs(row, t, 0, tp)
    keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    toks, nkeys = sample_tokens(
        jnp.broadcast_to(jnp.asarray(row), (n, v)), jnp.asarray(keys),
        jnp.full((n,), t, jnp.float32), jnp.zeros((n,), jnp.int32),
        jnp.full((n,), tp, jnp.float32))
    counts = np.bincount(np.asarray(toks), minlength=v)
    assert _tv(counts, p_ref) < 0.05
    # truncated support respected exactly, not just statistically
    assert counts[p_ref == 0.0].sum() == 0
    assert not np.array_equal(np.asarray(nkeys), keys)


def test_verify_emission_marginal_is_target_distribution():
    """The rejection-sampling guarantee, empirically: with a fixed
    point-mass proposal, the FIRST emitted token's marginal equals the
    target distribution, and the acceptance fraction equals p(t0)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.sampling import sample_or_verify

    rng = np.random.default_rng(5)
    v, n = 32, 4096
    logits = (rng.normal(size=(2, v)) * 2).astype(np.float32)  # (R=2, V)
    t = 0.9
    p_ref = _oracle_probs(logits[0], t, 0, 1.0)
    t0 = int(np.argsort(p_ref)[-3])  # a mid-mass proposal
    keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    toks, n_emit, _ = sample_or_verify(
        jnp.broadcast_to(jnp.asarray(logits), (n, 2, v)),
        jnp.full((n, 1), t0, jnp.int32), jnp.ones((n,), jnp.int32),
        jnp.asarray(keys), jnp.full((n,), t, jnp.float32),
        jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32))
    toks, n_emit = np.asarray(toks), np.asarray(n_emit)
    counts = np.bincount(toks[:, 0], minlength=v)
    assert _tv(counts, p_ref) < 0.05
    accept_frac = float((n_emit == 2).mean())
    assert abs(accept_frac - p_ref[t0]) < 0.05


# -- sampled speculative engine runs --------------------------------------

def test_spec_sampled_reproducible_and_fetchless(tiny_model,
                                                 garbage_draft):
    """Seeded sampled requests through the speculative engine are
    reproducible across engines (per-request device RNG streams), and
    the whole run fetches zero logits."""
    m = tiny_model
    prompts = _prompts(np.random.default_rng(8), m.config.vocab_size,
                       [5, 7, 4])
    sp = [SamplingParams(max_new_tokens=6, temperature=0.8, top_p=0.9,
                         seed=100 + i) for i in range(len(prompts))]

    def run_once():
        eng = LLMEngine(m, EngineConfig(block_size=4, draft_model=m,
                                        num_spec_tokens=2))
        rids = [eng.add_request(p, sampling=s)
                for p, s in zip(prompts, sp)]
        _run(eng)
        return eng, [eng.get_request(r).generated for r in rids]

    eng1, out1 = run_once()
    eng2, out2 = run_once()
    assert out1 == out2
    assert eng1.num_logits_fetches == 0 and eng2.num_logits_fetches == 0
    assert eng1.num_sampled_steps > 0
    assert eng1.num_spec_proposed > 0
    assert 0.0 <= eng1.spec_acceptance_rate <= 1.0
