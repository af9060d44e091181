"""Olmo Hybrid (Gated DeltaNet linear attention beside QK-normed full
attention in Olmo's post-norm block) held to the plain reference
(refs/olmo_hybrid_ref.py), and the gated delta rule's chunked form and
decode kernel held to its token-by-token recurrence. Tiny widths with the
published layout: two periods of three linear layers and a full one,
four heads, block 4. Logits, not tokens, wherever the engine's inputs can
be replayed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                           OlmoHybridForCausalLM)
from paddle_tpu.ops.gated_delta import ragged_gated_delta
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from refs import olmo_hybrid_ref as ref

BS = 4
# float32 logits of ~3 through eight post-normed layers: the chunked and
# the token-by-token delta rule, rsqrt against 1 / sqrt, differ by ~2e-4
TOL = 5e-4


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def build(**kw):
    paddle.seed(3)
    m = OlmoHybridForCausalLM(OlmoHybridConfig.tiny(**kw))
    m.eval()
    rng = np.random.default_rng(0)
    for name, p in m.named_parameters():
        if name.endswith("_norm_w"):
            a = np.asarray(p._data)
            p._data = jnp.asarray(1 + 0.3 * rng.standard_normal(a.shape),
                                  a.dtype)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def ref_params(m):
    return {"embed": m.embed_tokens.weight._data,
            "norm_w": m.final_norm_w._data, "lm_head": m.lm_head._data,
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


def prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(1, 160, n)]
            for i, n in enumerate(lengths)}


# -- the model against the reference ------------------------------------------
@pytest.mark.parametrize("neg_eigval", [True, False])
def test_forward_matches_reference(neg_eigval):
    m = build(linear_allow_neg_eigval=neg_eigval)
    toks = np.random.default_rng(2).integers(0, 160, (2, 37))
    got = np.asarray(m.forward(toks)._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(m, toks[b]),
                                   atol=TOL, rtol=0)


def test_negative_eigenvalues_reach_the_logits(model):
    """beta = 2 sigmoid(.) is not sigmoid(.): the same weights with
    ``linear_allow_neg_eigval`` off give other logits."""
    other = build(linear_allow_neg_eigval=False)
    toks = np.random.default_rng(2).integers(0, 160, (1, 21))
    a = np.asarray(model.forward(toks)._data)
    b = np.asarray(other.forward(toks)._data)
    assert np.abs(a - b).max() > 1e-2


def test_config_refuses_what_it_does_not_build():
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig.tiny(layer_types=["full_attention"] * 3)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        OlmoHybridConfig.tiny(tie_word_embeddings=True)
    with pytest.raises(ValueError, match="multi-head"):
        OlmoHybridConfig.tiny(num_key_value_heads=2)


def test_published_widths_parameter_count_and_cache():
    """Two periods (8 layers) at the published widths: 2,435,748,072
    parameters from the shapes alone (nothing is built); the state a
    linear layer keeps in a slot is (96, 30 x 192) float32 and 3 x 11,520
    convolution inputs, the full layers' K/V 30 x 128 lanes folded."""
    c = OlmoHybridConfig(num_hidden_layers=8,
                         layer_types=OlmoHybridConfig().layer_types[:8])
    h, i, v = c.hidden_size, c.intermediate_size, c.vocab_size
    qk, vw = c.linear_widths
    mlp = 3 * h * i + 2 * h
    linear = (h * (2 * qk + vw) + 4 * (2 * qk + vw) + h * 2 * 30 + h * vw
              + 192 + vw * h + 30 + 30)
    full = 4 * h * h + 2 * h
    assert 6 * (linear + mlp) + 2 * (full + mlp) + 2 * v * h + h \
        == 2_435_748_072
    spec = OlmoHybridForCausalLM.cache_spec(type("M", (), {"config": c})())
    assert spec["kv_shape"] == (3840,)
    kinds = [lay["kind"] for lay in spec["layers"]]
    assert kinds == ["state", "state", "state", "full"] * 2
    assert spec["layers"][0]["shapes"] == {
        "ssm": ((96, 5760), "float32"), "conv": ((3, 11520), None)}


def test_built_parameter_count_is_the_shapes(model):
    c = model.config
    h, qk, vw = c.hidden_size, *c.linear_widths
    heads = c.linear_num_key_heads
    mlp = 3 * h * c.intermediate_size + 2 * h
    linear = (h * (2 * qk + vw) + 4 * (2 * qk + vw) + 2 * h * heads
              + h * vw + c.linear_value_head_dim + vw * h + 2 * heads)
    full = 4 * h * h + 2 * h
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) == (
        6 * (linear + mlp) + 2 * (full + mlp) + 2 * c.vocab_size * h + h)


# -- the op: chunked form and decode kernel against the token recurrence ------
def _delta_rows(new, ctx, h=4, dk=8, dv=16, seed=0, t=96):
    """A step's row table and inputs: ``new`` tokens a row, ``ctx`` its
    length after the step (carried state where ctx > new)."""
    rng = np.random.default_rng(seed)
    s = len(new)
    slots = rng.permutation(s + 2)[:s].astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(new)]).astype(np.int32)
    q = rng.standard_normal((t, h, dk)).astype(np.float32)
    k = rng.standard_normal((t, h, dk)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((t, h, dv)).astype(np.float32)
    g = (-0.4 * np.abs(rng.standard_normal((t, h)))).astype(np.float32)
    beta = (2 * rng.random((t, h))).astype(np.float32)
    state = (0.3 * rng.standard_normal((s + 3, dk, h * dv))).astype(
        np.float32)
    return dict(q=q, k=k, v=v, g=g, beta=beta, state=state, slots=slots,
                cu=cu, ctx=np.asarray(ctx, np.int32), ns=s)


def _recurrence(a):
    """The rule token by token in float64, from each row's slot or zero."""
    out = np.zeros(a["v"].shape, np.float64)
    state = a["state"].astype(np.float64).copy()
    _, h, dk = a["k"].shape
    dv = a["v"].shape[2]
    for i in range(a["ns"]):
        lo, hi = a["cu"][i], a["cu"][i + 1]
        if hi == lo:
            continue
        mine = state[a["slots"][i]].reshape(dk, h, dv).transpose(1, 0, 2)
        s = mine if a["ctx"][i] > hi - lo else np.zeros_like(mine)
        for t in range(lo, hi):
            s = np.exp(a["g"][t])[:, None, None] * s
            kv = np.einsum("hkv,hk->hv", s, a["k"][t])
            s = s + np.einsum("hk,hv->hkv", a["k"][t], a["beta"][t][:, None]
                              * (a["v"][t] - kv))
            out[t] = np.einsum("hkv,hk->hv", s, a["q"][t])
        state[a["slots"][i]] = s.transpose(1, 0, 2).reshape(dk, h * dv)
    return out, state


def _run_op(a, impl, chunk):
    return ragged_gated_delta(
        *(jnp.asarray(a[n]) for n in ("q", "k", "v", "g", "beta", "state",
                                      "slots", "cu", "ctx")),
        jnp.int32(a["ns"]), impl=impl, chunk=chunk)


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("new,ctx", [
    # chunk rows carrying state in, starting mid-chunk (ctx - new not a
    # multiple of the chunk), decode rows, a fresh row, a fresh 1-token row
    ([37, 1, 5, 1, 23], [40, 9, 5, 1, 50]),
    ([1] * 6 + [70], [3, 8, 1, 12, 5, 2, 75]),
], ids=["chunks-and-decodes", "decodes-then-long-chunk"])
def test_chunked_form_matches_token_recurrence(new, ctx, chunk):
    a = _delta_rows(new, ctx)
    assert (a["beta"] > 1).any() and (a["beta"] < 1).any()
    want, want_state = _recurrence(a)
    o, state = _run_op(a, "xla", chunk)
    t = a["cu"][a["ns"]]
    np.testing.assert_allclose(np.asarray(o)[:t], want[:t], atol=2e-5,
                               rtol=0)
    assert not np.asarray(o)[t:].any()
    # every slot but the scratch one: the rows' written, the others kept
    np.testing.assert_allclose(np.asarray(state)[:-1], want_state[:-1],
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("dv", [16, 64], ids=["group4x16", "group2x64"])
def test_decode_kernel_interpreted_matches_the_xla_step(dv):
    """The Pallas kernel (interpreted) against the ``jax.numpy`` step:
    decode rows carrying state, a fresh decode row, chunk and padding rows
    sent to the scratch slot; heads in groups of 128 lanes (64 lanes a
    head: two a group) or of what divides the heads."""
    a = _delta_rows([1, 1, 9, 1, 1, 0], [7, 1, 20, 4, 30, 0], dv=dv,
                    seed=4)
    a["ns"] = 5
    o_x, s_x = _run_op(a, "xla", 16)
    o_k, s_k = _run_op(a, "interpret", 16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_x), atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(s_k)[:-1], np.asarray(s_x)[:-1],
                               atol=1e-5, rtol=0)


def test_model_on_the_interpreted_kernel_matches_the_xla_route(model):
    a = build(delta_impl="interpret")
    toks = np.random.default_rng(6).integers(0, 160, (2, 9))
    np.testing.assert_allclose(np.asarray(a.forward(toks)._data),
                               np.asarray(model.forward(toks)._data),
                               atol=1e-5, rtol=0)


# -- 30 K/V heads with one query head each through the ragged kernel ----------
@pytest.mark.parametrize("new,ctx_live,mb,t", [
    ([17, 1, 6], [25, 30, 6], 4, 24),
    ([1] * 12, [3 + 5 * i for i in range(12)], 8, 16),
], ids=["chunk-decode-chunk", "twelve-decode-rows"])
def test_thirty_kv_heads_of_one_query_head_interpreted(new, ctx_live, mb, t):
    """The full layers' call: 30 K/V heads of 128 lanes, folded, ``rep``
    1; decode windows and a chunk's tile through the interpreted kernel
    against the ``jnp`` reference."""
    rng = np.random.default_rng(0)
    s, h, d, bs = len(new), 30, 128, 8
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((t, h, d)), f32)
    k = jnp.asarray(rng.standard_normal((t, h, d)), f32)
    v = jnp.asarray(rng.standard_normal((t, h, d)), f32)
    kc = jnp.asarray(rng.standard_normal((s * mb, bs, h * d)), f32)
    vc = jnp.asarray(rng.standard_normal((s * mb, bs, h * d)), f32)
    bt = jnp.asarray(rng.permutation(s * mb).reshape(s, mb), jnp.int32)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(new)]), jnp.int32)
    ctx = jnp.asarray(ctx_live, jnp.int32)
    outs = [ragged_paged_attention(q, k, v, kc, vc, bt, cu, ctx,
                                   jnp.int32(s), impl=impl)[0]
            for impl in ("ref", "interpret")]
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[0]),
                               atol=2e-5, rtol=0)


# -- the engine's ragged path -------------------------------------------------
class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` (outside any jit) on the step's
    own inputs and the cache as it is, and keeps every live row's logits
    by request and context length."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits = {}
        engine._jstep_ragged = self

    def __call__(self, *args):
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        lg, _ = self.engine.model.forward_ragged(ids, cache, tables, bt,
                                                 cu, ctx, nseq)
        lg = np.asarray(lg)
        running = {self.engine.block_manager.state_slot(r.request_id):
                   r.request_id for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            rid = running[int(tables["slots"][i])]
            self.logits[(rid, int(ctx[i]))] = lg[i]
        return self.real(*args)


def serve(model, requests, new_tokens=5, **ecfg):
    kw = dict(block_size=BS, max_num_seqs=3, max_model_len=96,
              max_batched_tokens=16)
    kw.update(ecfg)
    eng = LLMEngine(model, EngineConfig(**kw))
    spy = LogitSpy(eng)
    for rid, p in requests.items():
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


def assert_against_reference(model, requests, gen, spy):
    """Every row that could yield a token (a prompt's last row, every
    decode row) equals the reference's logits at that position of the
    request's own history."""
    checked = 0
    for rid, p in requests.items():
        hist = list(p) + gen[rid]
        want = ref_logits(model, hist)
        for n in range(len(p), len(hist)):
            np.testing.assert_allclose(spy.logits[(rid, n)], want[n - 1],
                                       atol=TOL, rtol=0, err_msg=f"{rid}@{n}")
            assert gen[rid][n - len(p)] == int(np.argmax(want[n - 1]))
            checked += 1
    return checked


def test_engine_chunked_prefill_decode_and_slot_reuse(model):
    """Five requests through three slots, prompts over the 16-token
    budget (chunk boundaries inside prompts): chunk-to-chunk state,
    decode through the cache, slots and blocks reused."""
    requests = prompts([30, 5, 41, 17, 9])
    gen, spy, eng = serve(model, requests)
    assert eng.cfg.prefix_cache is False
    assert assert_against_reference(model, requests, gen, spy) == 25
    assert len(eng._seen_shapes) == 1
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0
    assert snap["preemptions"] == 0


def test_a_reused_slot_starts_from_zero(model):
    """Every slot of the state pool filled with large values before the
    first step: a request that starts at position 0 ignores what its slot
    holds, so its logits are still the reference's."""
    requests = prompts([11, 3, 19, 6], seed=3)
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                        max_model_len=96,
                                        max_batched_tokens=16))
    eng._cache = [{k: jnp.full_like(a, 7.0) for k, a in c.items()}
                  if isinstance(c, dict) else c for c in eng._cache]
    spy = LogitSpy(eng)
    for rid, p in requests.items():
        eng.add_request(rid, p, SamplingParams(max_new_tokens=4))
    gen = {}
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                gen[out.request_id] = list(out.generated)
                eng.release_request(out.request_id)
    assert assert_against_reference(model, requests, gen, spy) == 16


def test_preemption_by_recompute_gives_the_same_logits(model):
    """A pool too small for three long requests: victims are preempted,
    come back from zero state and recompute, and every logit they yield
    is still the reference's."""
    requests = prompts([22, 27, 18], seed=4)
    gen, spy, eng = serve(model, requests, new_tokens=14, num_blocks=22)
    assert eng.scheduler.num_preemptions > 0
    assert assert_against_reference(model, requests, gen, spy) == 42


def test_state_pool_bytes_are_counted(model):
    from paddle_tpu import profiler

    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=3,
                                        max_model_len=96,
                                        max_batched_tokens=16))
    c = model.config
    per_slot = 6 * (c.linear_key_head_dim * c.linear_widths[1] * 4
                    + 3 * (2 * c.linear_widths[0] + c.linear_widths[1]) * 4)
    assert eng.metrics.snapshot()["serving_state_bytes_held"] == 0
    eng.add_request("a", [5, 6, 7], SamplingParams(max_new_tokens=3))
    eng.step()
    assert eng.metrics.snapshot()["serving_state_bytes_held"] == per_slot
    held = [v for k, v in profiler.counters().items()
            if k.startswith("serving/state_bytes_held#")]
    assert per_slot in held
