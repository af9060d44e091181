"""The LongCat-Flash decoder (models/longcat.py) held to its plain
reference (refs/longcat_ref.py: expanded attention, a masked loop over the
held experts plus the identity term): the whole-sequence forward; the
shortcut-connected double layer; the softmax router over routed AND
identity experts and its edge cases; the shares of an expert layer; the
step's two counters; chunked prefill and decode through two latent pools
a layer in the serving engine. And the sigmoid router and the expert
layer without identity experts, pinned to what they computed before
identity experts existed. Tiny widths with the published ratios: 2 double
layers, 8 routed + 4 identity experts, top-3, block 8. Logits, not
tokens, wherever the inputs can be replayed."""
import ast
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models.longcat import LongCatConfig, LongCatForCausalLM
from paddle_tpu.ops import moe
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from refs import longcat_ref as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "longcat-flash-chat-d4.json")
BS = 8
# float32 on both sides at tiny widths: what is left is the order of the
# sums (blocked attention, the grouped product's row order)
TOL = 2e-4


def randomize(model, seed=0):
    """Norm weights away from 1, so that a dropped one shows."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name or name.split(".")[-1] in (
                "in0_w", "post0_w", "in1_w", "post1_w"):
            a = np.asarray(p._data)
            p._data = jnp.asarray(1 + 0.1 * rng.standard_normal(a.shape),
                                  a.dtype)


def build(seed=3, **kw):
    paddle.seed(seed)
    m = LongCatForCausalLM(LongCatConfig.tiny(**kw))
    m.eval()
    randomize(m)
    return m


def ref_weights(model):
    return {"embed": model.embed_tokens.weight._data,
            "layers": [lay.weights() for lay in model.layers],
            "norm_w": model.final_norm.weight._data,
            "lm_head": model.lm_head._data}


def ref_cfg(c):
    cfg = {k: getattr(c, k) for k in ref.KEYS if k != "first_expert"}
    cfg["first_expert"] = c.experts_held[0]
    return cfg


@pytest.fixture(scope="module")
def model():
    return build()


_REF_CACHE = {}


def ref_forward(model, tokens):
    """(logits at every position, [info per layer]) of the reference."""
    key = (id(model), tuple(tokens))
    if key not in _REF_CACHE:
        logits, infos = ref.forward(ref_weights(model), jnp.asarray(tokens),
                                    ref_cfg(model.config), block=1024)
        _REF_CACHE[key] = (np.asarray(logits), infos)
    return _REF_CACHE[key]


def prompts_of(lengths, seed=5, vocab=160):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(0, vocab, n)]
            for i, n in enumerate(lengths)}


def rel_err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def moe_weights(rng, d, f, e, zero):
    """An expert layer's weights in float32: ``e`` routed experts, a
    router ``e + zero`` wide, its bias at a tenth of p's spread."""
    return {"router": jnp.asarray(rng.standard_normal((d, e + zero)) / 3,
                                  jnp.float32),
            "router_bias": jnp.asarray(
                0.002 * rng.standard_normal(e + zero), jnp.float32),
            "experts_gate_up": jnp.asarray(
                0.2 * rng.standard_normal((e, d, 2 * f)), jnp.float32),
            "experts_down": jnp.asarray(
                0.2 * rng.standard_normal((e, f, d)), jnp.float32)}


# -- the reference and its copy; the configuration ------------------------
def test_reference_copies_define_the_same_functions():
    def functions(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        return {n.name: ast.dump(n) for n in tree.body
                if isinstance(n, ast.FunctionDef)}

    mine = functions(os.path.join(HERE, "refs", "longcat_ref.py"))
    theirs = functions(os.path.join(HERE, "..", "benchmark",
                                    "reference_longcat.py"))
    assert mine and mine == theirs


def parameters(c):
    """Parameters of a configuration, counted from its widths: per layer
    two attentions, two dense FFNs, the router and its bias, four norms,
    the held experts; the embedding and head slices; the final norm."""
    heads, dn, dr, dv, rq, rank = c.attn_dims
    d, f, fe = c.hidden_size, c.ffn_hidden_size, c.expert_ffn_hidden_size
    attn = (d * rq + rq + rq * heads * (dn + dr) + d * (rank + dr) + rank
            + rank * heads * (dn + dv) + heads * dv * d)
    layer = (2 * attn + 2 * 3 * d * f + (d + 1) * c.router_width + 4 * d
             + c.experts_held[1] * 3 * d * fe)
    return c.num_layers * layer + 2 * c.vocab_held[1] * d + d


def test_config_file_holds_the_row_and_the_cut():
    with open(CONFIG) as f:
        m = json.load(f)
    # the catalog row's numbers under its own keys; the three cut keys
    # are this chip's share
    assert (m["hidden_size"], m["ffn_hidden_size"],
            m["expert_ffn_hidden_size"], m["num_attention_heads"],
            m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["moe_topk"],
            m["zero_expert_num"], m["routed_scaling_factor"]) == (
        6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 12, 256, 6)
    assert sorted(m["reduced"]) == ["n_routed_experts", "num_layers",
                                    "vocab_size"]
    assert (m["num_layers"], m["n_routed_experts"], m["vocab_size"]) == (
        4, 16, 16384)
    pub = m["published"]
    c = LongCatConfig(
        num_layers=m["num_layers"],
        n_routed_experts=pub["n_routed_experts"],
        experts_held=(m["first_expert"], m["n_routed_experts"]),
        vocab_size=pub["vocab_size"],
        vocab_held=(m["first_row"], m["vocab_size"]))
    assert c.attn_dims == (64, 128, 64, 128, 1536, 512)
    assert (c.router_width, c.latent_lanes) == (768, 640)
    # 5,172,749,312 parameters (the runner prints
    # the count of the model it built on the chip)
    assert parameters(c) == 5_172_749_312


def test_parameter_count_is_the_built_models(model):
    built = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert built == parameters(model.config)
    share = build(experts_held=(2, 4), vocab_held=(16, 100))
    assert sum(int(np.prod(p.shape)) for p in share.parameters()) == (
        parameters(share.config))


def test_config_refuses_what_is_not_built():
    for bad in (dict(attention_method="GQA"), dict(attention_bias=True),
                dict(zero_expert_type="copy")):
        with pytest.raises(ValueError, match="does not implement"):
            LongCatConfig.tiny(**bad)
    with pytest.raises(ValueError, match="more experts per token"):
        LongCatConfig.tiny(moe_topk=13)
    for bad in (dict(experts_held=(6, 4)), dict(vocab_held=(100, 100))):
        with pytest.raises(ValueError, match="is no part of"):
            LongCatConfig.tiny(**bad)


# -- (a) the whole-sequence forward ---------------------------------------
@pytest.mark.parametrize("length", [5, 16, 47])
def test_forward_logits_match_reference(model, length):
    tokens = prompts_of([length], seed=length)["r0"]
    want, infos = ref_forward(model, tokens)
    got = np.asarray(model.forward(np.asarray([tokens]))._data)[0]
    assert rel_err(got, want) <= TOL
    # the tiny router picks identity experts as well as routed ones
    sets = np.concatenate([np.asarray(i["sets"]) for i in infos])
    assert (sets >= 8).any() and (sets < 8).any()


def _moved_layer(where):
    """The reference's double layer with the expert branch MOVED: fed from
    the second post-attention norm, or added before the second attention
    (so that it flows through it)."""
    def run_layer(p, x, cfg, routing=None, block=512):
        with jax.default_matmul_precision(ref.HIGHEST):
            eps = cfg["rms_norm_eps"]
            a1 = x + ref.attention(ref.rms_norm(x, p["in0_w"], eps),
                                   ref.sub(p, "attn0_"), cfg, block)
            u = ref.rms_norm(a1, p["post0_w"], eps)
            b1 = a1 + ref.swiglu(u, p["mlp0_gate_up"], p["mlp0_down"])
            if where == "before_second_attention":
                b1 = b1 + ref.moe(u, p, cfg)[0]
            a2 = b1 + ref.attention(ref.rms_norm(b1, p["in1_w"], eps),
                                    ref.sub(p, "attn1_"), cfg, block)
            v = ref.rms_norm(a2, p["post1_w"], eps)
            y = a2 + ref.swiglu(v, p["mlp1_gate_up"], p["mlp1_down"])
            if where == "from_second_norm":
                y = y + ref.moe(v, p, cfg)[0]
            return y, {}
    return run_layer


@pytest.mark.parametrize("where", ["from_second_norm",
                                   "before_second_attention"])
def test_the_shortcut_shows_when_the_branch_is_moved(model, monkeypatch,
                                                     where):
    """m comes from u = RMSNorm_post0(a1) and joins after FFN_1: the
    program agrees with the reference (above) and disagrees, by far more
    than the tolerance, with a reference whose branch is moved."""
    tokens = prompts_of([23], seed=23)["r0"]
    got = np.asarray(model.forward(np.asarray([tokens]))._data)[0]
    monkeypatch.setattr(ref, "run_layer", _moved_layer(where))
    moved, _ = ref.forward(ref_weights(model), jnp.asarray(tokens),
                           ref_cfg(model.config))
    assert rel_err(got, np.asarray(moved)) > 50 * TOL


# -- (b) the router -------------------------------------------------------
def test_softmax_router_selects_by_p_plus_bias_and_weighs_by_scaled_p():
    rng = np.random.default_rng(1)
    t, d, e = 32, 16, 12
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.standard_normal(e), jnp.float32)
    chosen, w, biased = moe.route_softmax_topk(u, w_r, bias, top_k=3,
                                               scale=6.0)
    logits = np.asarray(u, np.float64) @ np.asarray(w_r, np.float64)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)          # softmax over the WHOLE width
    np.testing.assert_allclose(np.asarray(biased), p + np.asarray(bias),
                               rtol=1e-5, atol=1e-7)
    want = np.argsort(-(p + np.asarray(bias)), axis=1, kind="stable")[:, :3]
    assert (np.sort(np.asarray(chosen), 1) == np.sort(want, 1)).all()
    # the weights are 6 p of the chosen: not normalised, no bias in them
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.take_along_axis(p, np.asarray(chosen), 1),
        rtol=1e-5, atol=1e-7)
    assert (np.asarray(w).sum(1) < 6.0).all()
    # the bias moves the selection: without it some sets differ
    plain, _, _ = moe.route_softmax_topk(u, w_r, bias * 0, top_k=3,
                                         scale=6.0)
    assert (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(chosen),
                                                     1)).any()


def _expert_layer(u, p, chosen, w, live, zero_from=8, **kw):
    return moe.dropless_expert_ffn(u, chosen, w, p["experts_gate_up"],
                                   p["experts_down"], live,
                                   zero_experts=zero_from, **kw)


@pytest.mark.parametrize("case", ["all_identity", "no_identity", "mixed"])
def test_identity_experts_add_w_times_u(case):
    """All picks identity: the layer is (sum w) u and no row is sorted;
    none identity: it is the routed experts alone, as without
    ``zero_experts``; mixed: the masked loop plus the identity term.
    Padding rows are counted nowhere and add nothing."""
    rng = np.random.default_rng(2)
    t, d, f, e, k = 24, 16, 8, 8, 3
    p = moe_weights(rng, d, f, e, 4)
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    if case == "all_identity":
        chosen = rng.integers(8, 12, (t, k))
    elif case == "no_identity":
        chosen = rng.integers(0, 8, (t, k))
    else:
        chosen = rng.integers(0, 12, (t, k))
    chosen = jnp.asarray(chosen, jnp.int32)
    live = jnp.arange(t) < t - 5
    out, rows = _expert_layer(u, p, chosen, w, live)
    out, rows = np.asarray(out), np.asarray(rows)
    want = np.asarray(ref.experts(u, p, chosen, w, 0, zero_from=8))
    np.testing.assert_allclose(out[:t - 5], want[:t - 5], rtol=2e-5,
                               atol=2e-6)
    assert not out[t - 5:].any()
    c = np.asarray(chosen)[:t - 5]
    assert rows.tolist() == np.bincount(c[c < 8], minlength=8).tolist()
    if case == "all_identity":
        assert rows.sum() == 0
        np.testing.assert_allclose(
            out[:t - 5], (np.asarray(w).sum(1)[:, None]
                          * np.asarray(u))[:t - 5], rtol=1e-6, atol=1e-7)
    if case == "no_identity":
        plain, _ = moe.dropless_expert_ffn(
            u, chosen, w, p["experts_gate_up"], p["experts_down"], live)
        np.testing.assert_array_equal(out, np.asarray(plain))
    counts = np.asarray(moe.zero_expert_counts(chosen, live, 8))
    assert counts.tolist() == [int((c >= 8).sum()), (t - 5) * k]


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Section 4 of the model-configs guide: the routed parts that all
    the shares give (4 shares of 4 experts, and 16 of one), with the
    identity term counted once (the share that adds it), add up to what
    the uncut reference gives for the whole layer."""
    rng = np.random.default_rng(0)
    t, d, f, e, zero, k = 40, 16, 8, 16, 8, 4
    p = moe_weights(rng, d, f, e, zero)
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    cfg = {"moe_topk": k, "routed_scaling_factor": 6.0,
           "n_routed_experts": e, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        whole, sets, _, _ = ref.moe(u, p, cfg)
        whole = np.asarray(whole)
        chosen, weights, _ = moe.route_softmax_topk(
            u, p["router"], p["router_bias"], top_k=k, scale=6.0)
        assert (np.asarray(chosen) >= e).any()
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), 1),
                                      np.sort(np.asarray(sets), 1))
        live = jnp.ones((t,), bool)
        for size in (4, 1):
            total, rows = 0.0, []
            for first in range(0, e, size):
                part, n = moe.dropless_expert_ffn(
                    u, chosen, weights,
                    p["experts_gate_up"][first:first + size],
                    p["experts_down"][first:first + size], live,
                    first_expert=first,
                    zero_experts=e if first == 0 else None)
                total = total + np.asarray(part)
                rows += np.asarray(n).tolist()
            np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
            c = np.asarray(chosen)
            assert rows == np.bincount(c[c < e], minlength=e).tolist()


# -- (c) the parent's expert layer, pinned ---------------------------------
def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# (name, rows, hidden, expert width, router width, held, first expert,
#  top-k, scale): the Kimi and dots3 tiny widths; the digests are what the
# code computed BEFORE the softmax router and identity experts were added
PINNED = [
    (("kimi", 24, 64, 32, 8, 8, None, 3, 2.446),
     ("5a67d5dc2761428a", "7a73d9a6d221c456")),
    (("dots3", 24, 64, 32, 8, 4, 2, 3, 1.0),
     ("220bcb038d559afd", "2d45c4a76eb12c00")),
]


@pytest.mark.parametrize("case,digests", PINNED,
                         ids=[c[0][0] for c in PINNED])
def test_sigmoid_router_and_expert_layer_are_bit_for_bit_the_parents(
        case, digests):
    _, t, d, f, e, held, first, k, scale = case
    ks = jax.random.split(jax.random.PRNGKey(41), 6)
    u = jax.random.normal(ks[0], (t, d), jnp.float32).astype(jnp.bfloat16)
    w_r = jax.random.normal(ks[1], (d, e), jnp.float32) * 0.2
    b = jax.random.normal(ks[2], (e,), jnp.float32) * 0.01
    gu = (jax.random.normal(ks[3], (held, d, 2 * f), jnp.float32)
          * 0.1).astype(jnp.bfloat16)
    dn = (jax.random.normal(ks[4], (held, f, d), jnp.float32)
          * 0.1).astype(jnp.bfloat16)
    live = jnp.arange(t) < t - 5
    chosen, w, biased = moe.route_sigmoid_topk(u, w_r, b, top_k=k,
                                               scale=scale, normalize=True)
    out, rows = moe.dropless_expert_ffn(u, chosen, w, gu, dn, live,
                                        first_expert=first)
    assert (_digest(chosen, w, biased), _digest(out, rows)) == digests


# -- (d) the engine -------------------------------------------------------
class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` on the step's own inputs and the
    cache as it is, and keeps every live row's logits by request and
    context length, the step's histogram, counters and chosen sets."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits, self.hists, self.counts, self.expected = {}, [], [], []
        engine._jstep_ragged = self

    def __call__(self, *args):
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        lg, _, hist, counts, routing = self.engine.model.forward_ragged(
            ids, cache, tables, bt, cu, ctx, nseq, return_routing=True)
        lg = np.asarray(lg)
        bm = self.engine.block_manager
        first = {bm.block_table(r.request_id)[0]: r.request_id
                 for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            self.logits[(first[int(bt[i, 0])], int(ctx[i]))] = lg[i]
        self.hists.append(np.asarray(hist))
        self.counts.append(np.asarray(counts))
        # the two counters, counted in NumPy from the chosen sets
        live = int(np.asarray(cu)[int(nseq)])
        sets = np.stack([np.asarray(r)[:live] for r in routing])
        zero = self.engine.model.config.n_routed_experts
        self.expected.append([int((sets >= zero).sum()), int(sets.size)])
        return self.real(*args)


def serve(model, prompts, new_tokens, **ecfg):
    kw = dict(block_size=BS, max_num_seqs=4, max_model_len=96,
              max_batched_tokens=16)
    kw.update(ecfg)
    eng = LLMEngine(model, EngineConfig(**kw))
    spy = LogitSpy(eng)
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


@pytest.mark.parametrize("lengths", [(5, 3), (47, 20), (5, 30, 41, 9, 26)],
                         ids=["short", "chunked", "mixed"])
def test_engine_logits_match_reference(model, lengths):
    """Prefill in chunks of 16, then decode, through two latent pools a
    layer, in mixed ragged batches (a continuing chunk, decode rows, new
    rows): every row that yields a token against the reference's full
    forward over the request's whole history."""
    prompts = prompts_of(lengths)
    gen, spy, eng = serve(model, prompts, 10)
    checked = 0
    for rid, prompt in prompts.items():
        tokens = list(prompt) + gen[rid]
        want, _ = ref_forward(model, tokens)
        for c in range(len(prompt), len(tokens)):
            assert rel_err(spy.logits[(rid, c)], want[c - 1]) <= TOL, (rid,
                                                                       c)
            checked += 1
        # greedy streams are the reference's argmax
        assert gen[rid] == np.argmax(want[len(prompt) - 1:-1], -1).tolist()
    assert checked == 10 * len(prompts)
    snap = eng.metrics.snapshot()
    assert snap["preemptions"] == 0 and len(eng._seen_shapes) == 1
    assert snap["kv_blocks_latent"] == 0 and eng.num_logits_fetches == 0
    assert len(eng._cache) == 2 * model.config.num_layers


def test_engine_counters_are_the_numpy_count(model):
    """``zero_rows`` and ``expert_assignments`` on every ``engine.post``
    span are the identity picks and all picks of the step's live rows,
    counted in NumPy from the chosen sets; the held experts' histogram
    rides beside them."""
    prompts = prompts_of((41, 7, 30))
    prof = profiler.Profiler(record_op_events=False).start()
    try:
        _, spy, eng = serve(model, prompts, 6)
    finally:
        prof.stop()
    post = [e["args"] for e in prof.host_events
            if e["name"] == "engine.post"]
    assert post and len(post) == len(spy.counts) == len(spy.expected)
    for args, counts, want, hist in zip(post, spy.counts, spy.expected,
                                        spy.hists):
        assert [args["zero_rows"], args["expert_assignments"]] == want
        assert counts.tolist() == want
        assert hist.shape == (2, 8)
        assert args["expert_rows"] + args["zero_rows"] == (
            args["expert_assignments"])
    assert 0 < sum(w[0] for w in spy.expected) < sum(
        w[1] for w in spy.expected)
    summed = eng.metrics.step_counters
    assert summed["zero_rows"] == sum(w[0] for w in spy.expected)
    assert summed["expert_assignments"] == sum(w[1] for w in spy.expected)


def test_engine_holds_a_share_of_the_experts_and_of_the_vocabulary():
    """The cell's cut at tiny widths: 4 of 8 routed experts and 100 of
    160 rows held; the logits over the slice against the reference given
    the same share."""
    share = build(experts_held=(2, 4), vocab_held=(20, 100))
    prompts = {k: [20 + t % 100 for t in v]
               for k, v in prompts_of((30, 9)).items()}
    gen, spy, eng = serve(share, prompts, 5)
    for rid, prompt in prompts.items():
        tokens = list(prompt) + gen[rid]
        want, _ = ref.forward(ref_weights(share),
                              jnp.asarray(tokens) - 20,
                              ref_cfg(share.config), block=1024)
        want = np.asarray(want)
        for c in range(len(prompt), len(tokens)):
            assert rel_err(spy.logits[(rid, c)], want[c - 1]) <= TOL
    assert all(h.shape == (2, 4) for h in spy.hists)


def test_engine_through_the_interpreted_kernels(model):
    """The same streams with the latent call and the grouped product
    interpreted."""
    prompts = prompts_of((30, 7))
    want, _, _ = serve(model, prompts, 3)
    kernels = build()
    kernels.config.ragged_attn_impl = "interpret"
    kernels.config.grouped_matmul_impl = "interpret"
    for (_, a), (_, b) in zip(kernels.named_parameters(),
                              model.named_parameters()):
        a._data = b._data
    got, spy, _ = serve(kernels, prompts, 3)
    assert got == want
    for rid, prompt in prompts.items():
        tokens = list(prompt) + got[rid]
        ref_logits, _ = ref_forward(model, tokens)
        for c in range(len(prompt), len(tokens)):
            assert rel_err(spy.logits[(rid, c)], ref_logits[c - 1]) <= TOL


def test_engine_refuses_the_prefix_cache_for_latent_pools(model):
    with pytest.raises(ValueError) as e:
        LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                      max_model_len=96, prefix_cache=True))
    assert "prefix_cache=True" in str(e.value) and "latent" in str(e.value)
