"""The latent-attention / routed-expert decoder (models/mla_moe.py) held
to the plain reference (refs/kimivl_ref.py, expanded attention, a masked
loop over the experts): the whole-sequence forward, absorbed against
expanded attention, the router, the dropless expert FFN and its grouped
product, the ragged attention op's latent mode, and the serving engine's
fourth kind of cache (one latent pool a layer) through chunked prefill
and decode. Tiny widths with the published ratios: 3 layers (1 dense),
8 experts top-3, latent rank 32 + rope 8, block 8. Logits, not tokens,
wherever the engine's inputs can be replayed."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import mla_moe
from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from refs import kimivl_ref as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BS = 8


def randomize(model, seed=0):
    """Norm weights away from 1, so that a dropped one shows."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith(("norm1_w", "norm2_w", "kv_norm_w",
                          "final_norm.weight")):
            a = np.asarray(p._data)
            p._data = jnp.asarray(1 + 0.1 * rng.standard_normal(a.shape),
                                  a.dtype)


def ref_weights(model):
    return {"embed": model.embed_tokens.weight._data,
            "layers": [lay.weights() for lay in model.layers],
            "norm_w": model.final_norm.weight._data,
            "lm_head": model.lm_head._data}


def ref_cfg(c):
    return {k: getattr(c, k) for k in ref.KEYS}


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = MlaMoeForCausalLM(MlaMoeConfig.tiny())
    m.eval()
    randomize(m)
    return m


_REF_CACHE = {}


def ref_forward(model, tokens):
    """(logits at every position, [info per layer]) of the reference."""
    key = (id(model), tuple(tokens))
    if key not in _REF_CACHE:
        logits, infos = ref.forward(ref_weights(model), jnp.asarray(tokens),
                                    ref_cfg(model.config))
        _REF_CACHE[key] = (np.asarray(logits), infos)
    return _REF_CACHE[key]


def prompts_of(lengths, seed=5, vocab=160):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(0, vocab, n)]
            for i, n in enumerate(lengths)}


class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` on the step's own inputs and the
    cache as it is, and keeps every live row's logits by request and
    context length, and each step's histogram."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits, self.hists, self.live_rows = {}, [], []
        engine._jstep_ragged = self

    def __call__(self, *args):
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        lg, _, hist = self.engine.model.forward_ragged(
            ids, cache, tables, bt, cu, ctx, nseq)
        lg = np.asarray(lg)
        bm = self.engine.block_manager
        first = {bm.block_table(r.request_id)[0]: r.request_id
                 for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            self.logits[(first[int(bt[i, 0])], int(ctx[i]))] = lg[i]
        self.hists.append(np.asarray(hist))
        self.live_rows.append(int(cu[int(nseq)]))
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


# -- the reference and its copy -------------------------------------------
def test_reference_copies_define_the_same_functions():
    def functions(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        return {n.name: ast.dump(n) for n in tree.body
                if isinstance(n, ast.FunctionDef)}

    mine = functions(os.path.join(HERE, "refs", "kimivl_ref.py"))
    theirs = functions(os.path.join(HERE, "..", "benchmark",
                                    "reference_kimivl.py"))
    assert mine and mine == theirs


def test_config_reads_the_public_keys_and_refuses_what_is_not_built():
    c = MlaMoeConfig()
    assert (c.latent_width, c.latent_lanes) == (576, 640)
    assert c.num_expert_layers == 26 and c.layer_kind(0) == "dense"
    assert c.layer_kind(1) == "moe"
    for bad in (dict(q_lora_rank=1536), dict(rope_scaling={"type": "yarn"}),
                dict(n_group=8, topk_group=4),
                dict(scoring_func="softmax"), dict(moe_layer_freq=2),
                dict(tie_word_embeddings=True)):
        with pytest.raises(ValueError, match="does not implement"):
            MlaMoeConfig(**bad)
    # query compression is built now, by the sibling that also has what
    # goes with it in the models that use it; the refusal says where
    with pytest.raises(ValueError, match="models/dots3.py"):
        MlaMoeConfig(q_lora_rank=1536)
    from paddle_tpu.models.dots3 import Dots3Config

    assert Dots3Config.tiny(q_lora_rank=40).attn_dims("full")[4] == 40


# -- (a) the whole-sequence forward ---------------------------------------
@pytest.mark.parametrize("length", [5, 16, 37])
def test_forward_logits_match_reference(model, length):
    tokens = prompts_of([length], seed=length)["r0"]
    got = np.asarray(model.forward(paddle.to_tensor(
        np.asarray([tokens])))._data)[0]
    want, infos = ref_forward(model, tokens)
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()
    # identical chosen sets at every expert layer and token
    t = len(tokens)
    bt = jnp.arange(-(-t // 16), dtype=jnp.int32)[None]
    cache = [jnp.zeros((bt.shape[1], 16, model.config.latent_lanes))
             for _ in model.layers]
    _, _, _, routing = model.forward_ragged(
        np.asarray(tokens, np.int32), cache, {}, bt,
        np.asarray([0, t], np.int32), np.asarray([t], np.int32),
        np.int32(1), return_routing=True)
    assert routing[0] is None and infos[0] == {}
    for l in range(1, len(model.layers)):
        np.testing.assert_array_equal(
            np.sort(np.asarray(routing[l]), axis=1),
            np.sort(np.asarray(infos[l]["sets"]), axis=1))


def test_absorbed_attention_is_expanded_attention(model):
    c = model.config
    p = model.layers[1].weights()
    t = 21
    u = jnp.asarray(np.random.default_rng(1).standard_normal(
        (t, c.hidden_size)), jnp.float32)
    want = ref.attention(u, p, ref_cfg(c))
    dims = (c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.kv_lora_rank)
    pos = jnp.arange(t)
    got, cache = mla_moe._mla(
        p, u, jnp.zeros((3, BS, c.latent_lanes)),
        jnp.arange(3, dtype=jnp.int32)[None], jnp.asarray([0, t]),
        jnp.asarray([t]), jnp.int32(1), model.rope_cos._data[pos],
        model.rope_sin._data[pos], dims=dims, eps=c.rms_norm_eps,
        impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # what a token leaves behind: [c | k_r | zero lanes], 40 numbers here
    entry = np.asarray(cache).reshape(-1, c.latent_lanes)[:t]
    assert np.abs(entry[:, :c.latent_width]).min() > 0
    assert not entry[:, c.latent_width:].any()


# -- (b) the router --------------------------------------------------------
def router_inputs(t=40, d=64, e=8, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((t, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((d, e)) * 0.2, jnp.float32))


def test_router_bias_moves_the_set_and_never_the_weights():
    u, w_g = router_inputs()
    zero = jnp.zeros((8,), jnp.float32)
    sets0, w0, _ = moe.route_sigmoid_topk(u, w_g, zero, top_k=3,
                                          scale=2.446)
    np.testing.assert_allclose(np.asarray(w0).sum(1), 2.446, rtol=1e-5)
    # a bias that lifts expert 5 into every set
    bias = zero.at[5].set(10.0)
    sets1, w1, sel = moe.route_sigmoid_topk(u, w_g, bias, top_k=3,
                                            scale=2.446)
    assert (np.asarray(sets1) == 5).any(axis=1).all()
    assert not (np.asarray(sets0) == 5).any(axis=1).all()
    np.testing.assert_allclose(np.asarray(w1).sum(1), 2.446, rtol=1e-5)
    # the weights are the scores WITHOUT the bias, over the chosen set
    s = np.asarray(jax.nn.sigmoid(u @ w_g))
    picked = np.take_along_axis(s, np.asarray(sets1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), picked / picked.sum(1, keepdims=True) * 2.446,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sel), s + np.asarray(bias),
                               rtol=1e-6)
    # the reference's router agrees, set for set
    rsets, rw, _, _ = ref.route(u, {"router": w_g, "router_bias": bias}, dict(
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.446))
    np.testing.assert_array_equal(np.asarray(rsets), np.asarray(sets1))
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w1), rtol=1e-5)


def test_router_without_normalisation_scales_the_raw_scores():
    u, w_g = router_inputs(seed=1)
    sets, w, _ = moe.route_sigmoid_topk(u, w_g, jnp.zeros((8,)), top_k=2,
                                        scale=1.5, normalize=False)
    s = np.asarray(jax.nn.sigmoid(u @ w_g))
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(s, np.asarray(sets), 1) * 1.5,
        rtol=1e-5)


# -- (c) the dropless expert FFN ------------------------------------------
def expert_weights(e=8, d=64, f=32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((e, d, 2 * f)) * 0.1,
                        jnp.float32),
            jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32))


def loop_over_experts(u, sets, w, gate_up, down):
    return np.asarray(ref.experts(
        u, {"experts_gate_up": gate_up, "experts_down": down}, sets, w))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("sizes", [[5, 0, 9, 1, 0, 0, 17, 8],
                                   [40, 0, 0, 0, 0, 0, 0, 0],
                                   [0, 0, 0, 0, 0, 0, 0, 3],
                                   [7, 6, 6, 6, 6, 6, 6, 5]],
                         ids=["uneven", "one_group", "last_group", "even"])
def test_grouped_matmul_is_a_per_expert_loop(sizes, impl):
    """The XLA route and the Pallas kernel (interpreted; 16-row tiles, so
    groups straddle tiles and tiles hold several groups) against a loop
    over the experts; 48 rows, so up to 8 rows belong to no group."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    w = expert_weights()[0]
    got = np.asarray(moe.grouped_matmul(
        x, w, jnp.asarray(sizes, jnp.int32), impl=impl, block_m=16))
    row = 0
    for e, n in enumerate(sizes):
        np.testing.assert_allclose(got[row:row + n],
                                   np.asarray(x[row:row + n] @ w[e]),
                                   rtol=1e-4, atol=1e-5)
        row += n


def test_grouped_matmul_work_items_cover_each_tile_group_pair_once():
    from paddle_tpu.ops.pallas.grouped_matmul import _work_items

    count, tile, group, starts, ends = _work_items(
        jnp.asarray([5, 0, 9, 1, 0, 0, 17, 8], jnp.int32), 48, 16)
    n = int(count[0])
    # rows 0-5 | 5-14 | 14-15 | 15-32 | 32-40 over tiles of 16 rows
    assert list(zip(np.asarray(tile)[:n], np.asarray(group)[:n])) == [
        (0, 0), (0, 2), (0, 3), (0, 6), (1, 6), (2, 7)]
    # entries past the count repeat the last item: no new block to fetch
    assert set(zip(np.asarray(tile)[n:], np.asarray(group)[n:])) == {(2, 7)}
    assert np.asarray(starts).tolist() == [0, 5, 5, 14, 15, 15, 15, 32]
    assert np.asarray(ends).tolist() == [5, 5, 14, 15, 15, 15, 32, 40]
    with pytest.raises(ValueError, match="unknown"):
        moe.grouped_matmul(jnp.zeros((16, 8)), jnp.zeros((2, 8, 8)),
                           jnp.asarray([1, 1]), impl="cuda")


@pytest.mark.parametrize("impl", [None, "interpret"])
def test_expert_ffn_matches_the_masked_loop_and_counts_live_rows_only(impl):
    u, w_g = router_inputs(t=40)
    gate_up, down = expert_weights()
    sets, w, _ = moe.route_sigmoid_topk(
        u, w_g, jnp.zeros((8,)), top_k=3, scale=2.446)
    live = jnp.arange(40) < 29               # 11 padding rows at the end
    u = u.at[29:].set(jnp.nan)               # padding may hold anything
    got, rows = moe.dropless_expert_ffn(u, sets, w, gate_up, down, live,
                                        impl=impl)
    want = loop_over_experts(u[:29], sets[:29], w[:29], gate_up, down)
    np.testing.assert_allclose(np.asarray(got)[:29], want, rtol=2e-4,
                               atol=1e-5)
    assert not np.asarray(got)[29:].any()    # nothing, not NaN
    assert int(rows.sum()) == 29 * 3
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(np.asarray(sets[:29]).reshape(-1),
                                      minlength=8))


def test_expert_ffn_is_dropless_under_a_forced_skew():
    u, _ = router_inputs(t=40)
    gate_up, down = expert_weights()
    # every row to expert 6 alone: 40 rows where an even share is 5
    sets = jnp.full((40, 1), 6, jnp.int32)
    w = jnp.full((40, 1), 2.446, jnp.float32)
    got, rows = moe.dropless_expert_ffn(u, sets, w, gate_up, down,
                                        jnp.ones((40,), bool))
    want = 2.446 * np.asarray(ref.swiglu(u, gate_up[6], down[6]))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-5)
    assert np.asarray(rows).tolist() == [0, 0, 0, 0, 0, 0, 40, 0]


# -- (d) the attention op's latent mode ------------------------------------
LATENT_ROWS = {"chunk+decode+fresh": ([0, 10, 11, 18, 18], [20, 33, 7, 0], 3),
               "decode_only": ([0, 1, 2, 3, 3], [40, 9, 17, 0], 3),
               "one_long_chunk": ([0, 32, 32, 32, 32], [45, 0, 0, 0], 1)}


def latent_inputs(width=256, heads=4, t=32, mb=6, seed=0):
    rng = np.random.default_rng(seed)
    nb = 4 * mb
    return dict(
        q=jnp.asarray(rng.standard_normal((t, heads, width)), jnp.float32),
        new=jnp.asarray(rng.standard_normal((t, width)), jnp.float32),
        cache=jnp.asarray(rng.standard_normal((nb, BS, width)),
                          jnp.float32),
        bt=rng.permutation(nb).astype(np.int32).reshape(4, mb))


def plain_latent_attention(q, entries, v_lanes, scale):
    """One sequence's new rows ``q`` (n, H, W) over all its entries
    (L, W): causal softmax, value = the entry's first lanes."""
    n, length = q.shape[0], entries.shape[0]
    out = []
    for j in range(n):
        keys = entries[:length - n + j + 1]
        s = np.einsum("hd,ld->hl", q[j], keys) * scale
        s = np.exp(s - s.max(-1, keepdims=True))
        out.append((s / s.sum(-1, keepdims=True)) @ keys[:, :v_lanes])
    return np.stack(out)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("name", sorted(LATENT_ROWS))
def test_latent_mode_is_plain_attention_over_one_cache(name, impl):
    cu, ctx, ns = LATENT_ROWS[name]
    k = latent_inputs()
    out, cache, none = ragged_paged_attention(
        k["q"], k["new"], None, k["cache"], None, k["bt"],
        np.asarray(cu, np.int32), np.asarray(ctx, np.int32), np.int32(ns),
        scale=0.1, impl=impl, v_lanes=128)
    assert none is None and out.shape == (32, 4, 128)
    out, cache = np.asarray(out), np.asarray(cache)
    for i in range(ns):
        r0, n = cu[i], cu[i + 1] - cu[i]
        entries = cache[k["bt"][i]].reshape(-1, 256)[:ctx[i]]
        # the step's own rows were written before they were read
        np.testing.assert_array_equal(entries[ctx[i] - n:],
                                      np.asarray(k["new"][r0:r0 + n]))
        want = plain_latent_attention(np.asarray(k["q"][r0:r0 + n]),
                                      entries, 128, 0.1)
        np.testing.assert_allclose(out[r0:r0 + n], want, rtol=2e-4,
                                   atol=2e-5)
    assert not out[cu[ns]:].any()            # padding rows read nothing


def test_latent_call_refuses_a_second_cache_and_a_window():
    k = latent_inputs()
    args = (k["bt"], np.asarray([0, 1, 1, 1, 1], np.int32),
            np.asarray([5, 0, 0, 0], np.int32), np.int32(1))
    with pytest.raises(ValueError, match="one cache"):
        ragged_paged_attention(k["q"], k["new"], None, k["cache"],
                               k["cache"], *args, v_lanes=128)
    # a window is taken now (a latent pool of window layers): the one
    # row at position 4 under a window of 1 attends to its own entry
    out, cache, _ = ragged_paged_attention(
        k["q"], k["new"], None, k["cache"], None, *args, v_lanes=128,
        window=1, impl="ref")
    np.testing.assert_allclose(
        np.asarray(out[0]), np.broadcast_to(np.asarray(k["new"][0, :128]),
                                            (4, 128)), rtol=1e-6)
    # a selection is no mode of this call any more (PR 36: it walked
    # every page under the mask); ops/pallas/sparse_latent_attention.py
    with pytest.raises(TypeError, match="selected"):
        ragged_paged_attention(k["q"], k["new"], None, k["cache"], None,
                               *args, v_lanes=128, window=4,
                               selected=jnp.ones((32, 6 * BS), jnp.int8))
    with pytest.raises(ValueError, match="lanes"):
        ragged_paged_attention(k["q"], k["new"], None, k["cache"], None,
                               *args, v_lanes=512)


# rows of the stream, as (stream rows a tile, cu_seqlens, context_lens),
# that put the latent kernel's tiles and page groups (two pages = 16 tokens
# here) to work at 16 heads, the stream's padding behind them
def _one_row_slots(n, seed=7):
    ctx = np.random.default_rng(seed).integers(1, 64, n)
    return list(range(n + 1)), [int(c) for c in ctx]


STREAM_ROWS = {
    "decode_row_alone_in_a_tile": (8, [0, 8, 9], [40, 29]),
    "thirty_one_row_slots_in_one_tile": (32, *_one_row_slots(30)),
    "chunk_crossing_two_tiles": (8, [0, 1, 14], [33, 50]),
    "chunk_tail_beside_decode_rows": (8, [0, 11, 12, 13, 14, 15],
                                      [43, 64, 1, 17, 32]),
    "context_ends_inside_a_group_and_a_page": (8, [0, 5, 6], [37, 21]),
    "one_slot_a_tile_to_the_last_row": (8, [0, 8, 16, 64], [8, 64, 48]),
    "nothing_live": (8, [0], []),
}


@pytest.mark.parametrize("window", [None, 10, 16],
                         ids=["causal", "window10", "window16"])
@pytest.mark.parametrize("name", sorted(STREAM_ROWS))
def test_latent_kernel_over_tiles_slots_and_page_groups(name, window,
                                                        monkeypatch):
    """The interpreted kernel against the plain float32 form, 16 heads of
    a stream row side by side: slots of one row computed on their own 16
    queries, chunks over whole tiles with the other slots' rows masked,
    contexts that end inside a page group and inside a page. Under a
    window the table's entries behind it are -1 (released) and the window
    of a row crosses a group's boundary wherever 16 does not divide its
    first key. Padding rows read zeros."""
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    tile, cu, ctx = STREAM_ROWS[name]
    ns, t, s, mb, heads = len(ctx), 64, 32, 8, 16
    monkeypatch.setattr(sla, "_PRODUCT_ROWS", tile * heads)
    monkeypatch.setattr(sla, "_group_tokens", lambda window: 16)
    # the jitted body reads the two at trace: unjitted, no trace of another
    # tiling serves this case, nor this one's a later test
    monkeypatch.setattr(sla, "_attend_pallas", sla._attend_pallas.__wrapped__)
    rng = np.random.default_rng(4)
    q = rng.standard_normal((t, heads, 256)).astype(np.float32)
    new = rng.standard_normal((t, 256)).astype(np.float32)
    pool = rng.standard_normal((s * mb, BS, 256)).astype(np.float32)
    bt = rng.permutation(s * mb).astype(np.int32).reshape(s, mb)
    cu = np.asarray(cu + [cu[-1]] * (s + 1 - len(cu)), np.int32)
    ctx = np.asarray(ctx + [0] * (s - ns), np.int32)
    table = bt.copy()
    if window is not None:
        for i in range(ns):
            # blocks wholly behind the first row's window are gone
            first = ctx[i] - (cu[i + 1] - cu[i])
            table[i, :max(first - window + 1, 0) // BS] = -1
    out, cache, _ = ragged_paged_attention(
        q, new, None, pool, None, table, cu, ctx, np.int32(ns), scale=0.1,
        impl="interpret", v_lanes=128, window=window)
    out, cache = np.asarray(out), np.asarray(cache)
    for i in range(ns):
        r0, n = cu[i], cu[i + 1] - cu[i]
        entries = cache[bt[i]].reshape(-1, 256)[:ctx[i]]
        np.testing.assert_array_equal(entries[ctx[i] - n:], new[r0:r0 + n])
        for j in range(n):
            pos = ctx[i] - n + j
            keys = entries[0 if window is None
                           else max(pos - window + 1, 0):pos + 1]
            sc = np.einsum("hd,ld->hl", q[r0 + j], keys) * 0.1
            sc = np.exp(sc - sc.max(-1, keepdims=True))
            np.testing.assert_allclose(
                out[r0 + j], (sc / sc.sum(-1, keepdims=True)) @ keys[:, :128],
                rtol=2e-4, atol=2e-5)
    assert not out[cu[ns]:].any()            # padding rows read nothing


# -- (e) the engine: one latent pool a layer -------------------------------
@pytest.mark.parametrize("lengths", [(5, 3), (37, 20), (5, 30, 17, 9, 26)],
                         ids=["short", "chunked", "mixed"])
def test_engine_logits_match_reference(model, lengths):
    """Prefill in chunks of 16, then decode, through the latent cache:
    every row that can yield a token against the reference's full
    forward over the request's whole history."""
    prompts = prompts_of(lengths)
    gen, spy, eng = serve(model, prompts, 10)
    checked = 0
    for rid, prompt in prompts.items():
        tokens = list(prompt) + gen[rid]
        want, _ = ref_forward(model, tokens)
        for c in range(len(prompt), len(tokens)):
            err = np.abs(spy.logits[(rid, c)] - want[c - 1]).max()
            assert err <= 2e-4 * np.abs(want).max(), (rid, c, err)
            checked += 1
    assert checked == 10 * len(lengths)
    assert eng.num_logits_fetches == 0
    snap = eng.metrics.snapshot()
    assert snap["kv_blocks_latent"] == 0 == snap["kv_blocks_full"]


def test_three_chunks_equal_one(model):
    prompts = prompts_of([40], seed=9)
    one, spy1, _ = serve(model, prompts, 6, max_batched_tokens=64)
    three, spy3, eng3 = serve(model, prompts, 6, max_batched_tokens=16)
    assert eng3.scheduler.num_prefill_chunks == 3 and one == three
    for c in range(40, 46):
        np.testing.assert_allclose(spy3.logits[("r0", c)],
                                   spy1.logits[("r0", c)], rtol=1e-4,
                                   atol=1e-4)


def test_mixed_step_routes_no_padding_row(model):
    """Every step of a served batch (16-row stream, mostly padding while
    decoding): the histogram of each expert layer sums to the live rows
    times top-k, whatever the padding rows hold."""
    _, spy, eng = serve(model, prompts_of([21, 6, 9], seed=4), 8)
    k = model.config.num_experts_per_tok
    assert any(n < 16 for n in spy.live_rows)
    for hist, n in zip(spy.hists, spy.live_rows):
        assert hist.shape == (2, 8)
        assert hist.sum(axis=1).tolist() == [n * k, n * k]
    snap = eng.metrics.snapshot()
    assert snap["moe_expert_rows"] == 2 * k * sum(spy.live_rows)
    assert 0 < snap["moe_experts_hit"] <= 2 * 8 * len(spy.hists)


def test_latent_spec_builds_one_donated_pool_a_layer(model):
    spec = model.cache_spec()
    assert {lay["kind"] for lay in spec["layers"]} == {"latent"}
    assert spec["expert_rows"] == (2, 8)
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                        max_model_len=32, num_blocks=8,
                                        donate_cache=True))
    assert eng._kcs is None and len(eng._cache) == 3
    for pool in eng._cache:                   # an array, not a (K, V) pair
        assert pool.shape == (8, BS, model.config.latent_lanes)
    assert eng.block_manager.latent and eng.cfg.prefix_cache is False
    before = eng._cache
    out = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
    assert len(out[0]) == 3
    # the whole pytree was donated to the step and came back in place
    assert all(old.is_deleted() for old in before)
    assert eng.metrics.snapshot()["kv_blocks_latent"] == 0


@pytest.mark.parametrize("knobs,name,why", [
    (dict(prefix_cache=True), "prefix_cache=True", "block-copy"),
    (dict(kv_tiers=True), "kv_tiers", "tier format"),
    (dict(swap_mode="host"), "swap_mode='host'", "host format"),
    (dict(tp_degree=2), "tp_degree > 1", "kv-head dim"),
    (dict(draft_model=object(), num_spec_tokens=2), "draft_model",
     "multi-row verify"),
])
def test_engine_refuses_by_name_for_the_reason_that_holds(model, knobs,
                                                          name, why):
    with pytest.raises(ValueError) as e:
        LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                      max_model_len=32, **knobs))
    msg = str(e.value)
    assert name in msg and "cache_spec" in msg and why in msg
    # a latent-only model is not refused for state it does not have
    assert "recurrent" not in msg and "window" not in msg


@pytest.mark.parametrize("call,name", [
    (lambda e: e.export_kv("r0"), "export_kv"),
    (lambda e: e.import_kv("r9", [1, 2], meta={}, payload=b""),
     "import_kv"),
    (lambda e: e.export_prefix("00"), "export_prefix"),
    (lambda e: e.import_prefix(meta={}, payload=b""), "import_prefix"),
    (lambda e: e.park_session("s"), "sessions"),
])
def test_engine_refuses_the_wire_and_sessions_by_name(model, call, name):
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                        max_model_len=32))
    with pytest.raises(ValueError) as e:
        call(eng)
    msg = str(e.value)
    assert name in msg and "cache_spec" in msg and "latent" in msg
    assert "recurrent" not in msg


def test_spans_and_snapshot_carry_the_new_counters(model):
    from paddle_tpu import profiler

    prof = profiler.Profiler(record_op_events=False).start()
    try:
        _, spy, eng = serve(model, prompts_of([30, 6], seed=16), 12)
    finally:
        prof.stop()
    disp = [e for e in prof.host_events if e["name"] == "engine.dispatch"]
    post = [e for e in prof.host_events if e["name"] == "engine.post"]
    assert disp and len(post) == len(spy.hists)
    assert all(e["args"]["latent_blocks"] > 0 for e in disp)
    assert all("win_blocks" not in e["args"] for e in disp)
    for e, hist in zip(post, spy.hists):
        a = e["args"]
        assert a["expert_rows"] == hist.sum()
        assert a["experts_hit"] == (hist > 0).sum()
        assert a["expert_rows_max"] == hist.max(axis=1).sum()
        assert a["expert_rows_even"] == pytest.approx(hist.sum() / 8)
        assert a["expert_rows_max"] >= a["expert_rows_even"]
    for key in ("kv_blocks_latent", "moe_expert_rows", "moe_experts_hit"):
        assert key in eng.metrics.snapshot()


def test_a_model_without_experts_counts_none():
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)

    paddle.seed(1)
    m = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    m.eval()
    eng = LLMEngine(m, EngineConfig(block_size=4, max_num_seqs=2,
                                    max_model_len=32))
    assert eng._expert_rows_shape is None and not eng.block_manager.latent
    eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
    snap = eng.metrics.snapshot()
    assert snap["moe_expert_rows"] == 0 == snap["kv_blocks_latent"]
