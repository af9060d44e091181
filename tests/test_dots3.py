"""The dots3-note-prev decoder (models/dots3.py) held to its plain
reference (refs/dots3_ref.py: expanded attention, a dense index score
matrix cut by ``jax.lax.top_k``, a masked loop over the held experts):
the whole-sequence forward; chunked prefill and decode through the paged
caches (latent + index keys under the main table, latent entries in the
window pool, released behind the window); faults that have to show; ties
in the index scores; the latent call under a selection (a kernel of its
own) and under a window and the index kernel against their ``jnp``
forms; the eight shares of an expert layer; the serving engine end to
end. Tiny widths with the published ratios:
5 layers (a dense full one, then full, sliding x 3), ``index_topk`` 8 and
a window of 5 against contexts of 40+, 8 experts of which 2-4 are held.
Logits, not tokens, wherever the inputs can be replayed."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import dots3
from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM
from paddle_tpu.ops import moe, sparse_index
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.ops.pallas.sparse_latent_attention import (
    sparse_latent_attention,
)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from refs import dots3_ref as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BS = 4
TOL = 2e-4


def randomize(model, seed=0):
    """Norm weights away from 1 (and the index key's bias from 0), so
    that a dropped one shows."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            a = np.asarray(p._data)
            base = 0.0 if name.endswith("_b") else 1.0
            p._data = jnp.asarray(base + 0.1 * rng.standard_normal(a.shape),
                                  a.dtype)


def build(seed=3, **kw):
    paddle.seed(seed)
    m = Dots3ForCausalLM(Dots3Config.tiny(**{"experts_held": (2, 4), **kw}))
    m.eval()
    randomize(m)
    return m


def with_config(model, **kw):
    """The same weights under another configuration (a fault, by
    configuration)."""
    other = build(**kw)
    for (_, a), (_, b) in zip(other.named_parameters(),
                              model.named_parameters()):
        a._data = b._data
    return other


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


def ref_forward(model, tokens, weights=None):
    """(logits at every position, [info per layer]) of the reference."""
    key = (id(model), tuple(tokens), weights is None)
    if key not in _REF_CACHE or weights is not None:
        logits, infos = ref.forward(weights or ref_weights(model),
                                    jnp.asarray(tokens),
                                    ref_cfg(model.config), block=1024)
        if weights is not None:
            return np.asarray(logits), infos
        _REF_CACHE[key] = (np.asarray(logits), infos)
    return _REF_CACHE[key]


def prompts_of(lengths, seed=5, vocab=160):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(0, vocab, n)]
            for i, n in enumerate(lengths)}


def rel_err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- the reference and its copy -------------------------------------------
def test_reference_copies_define_the_same_functions():
    def functions(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        return {n.name: ast.dump(n) for n in tree.body
                if isinstance(n, ast.FunctionDef)}

    mine = functions(os.path.join(HERE, "refs", "dots3_ref.py"))
    theirs = functions(os.path.join(HERE, "..", "benchmark",
                                    "reference_dots3.py"))
    assert mine and mine == theirs


def test_config_reads_the_row_and_refuses_what_is_not_built():
    c = Dots3Config(num_hidden_layers=5, layer_types=[
        dots3.FULL, dots3.FULL, dots3.SLIDING, dots3.SLIDING, dots3.SLIDING],
        experts_held=(0, 32), vocab_held=(0, 19008))
    assert c.attn_dims("full") == (128, 128, 64, 128, 1024, 512)
    assert c.attn_dims("sliding") == (64, 192, 64, 128, 1024, 1024)
    assert (c.latent_lanes("full"), c.latent_lanes("sliding"),
            c.index_lanes) == (640, 1152, 128)
    assert c.full_layers == [0, 1] and c.num_expert_layers == 4
    assert [c.ffn_kind(l) for l in range(3)] == ["dense", "moe", "moe"]
    tiny = dict(Dots3Config.tiny().__dict__)
    for bad in (dict(rope_scaling={"type": "yarn"}),
                dict(n_group=8, topk_group=4), dict(scoring_func="softmax"),
                dict(moe_layer_freq=2), dict(tie_word_embeddings=True),
                dict(attention_gate_type="elementwise"),
                dict(swa_num_key_value_heads=1),
                dict(layer_types=["full_attention"] * 4),
                dict(layer_types=["linear_attention"] * 5),
                dict(attention_bias=True), dict(hidden_act="gelu"),
                dict(topk_method="greedy")):
        with pytest.raises(ValueError, match="does not implement"):
            Dots3Config(**{**tiny, **bad})
    for bad in (dict(experts_held=(6, 4)), dict(vocab_held=(100, 100)),
                dict(experts_held=(0, 0))):
        with pytest.raises(ValueError, match="is no part of"):
            Dots3Config(**{**tiny, **bad})


# -- (a) the whole-sequence forward ---------------------------------------
@pytest.mark.parametrize("length", [5, 16, 47])
def test_forward_logits_match_reference(model, length):
    tokens = prompts_of([length], seed=length)["r0"]
    got = np.asarray(model.forward(paddle.to_tensor(
        np.asarray([tokens])))._data)[0]
    want, infos = ref_forward(model, tokens)
    assert rel_err(got, want) <= TOL
    # identical expert sets and identical index sets at every layer
    t = len(tokens)
    mb = -(-t // 16)
    bt = jnp.arange(mb, dtype=jnp.int32)[None]
    _, _, hist, counts, routing, selections = model.forward_ragged(
        np.asarray(tokens, np.int32),
        model.empty_cache(mb, mb, 16, jnp.float32), {"window": bt}, bt,
        np.asarray([0, t], np.int32), np.asarray([t], np.int32),
        np.int32(1), return_routing=True)
    assert routing[0] is None and "sets" not in infos[0]
    for l in range(1, len(model.layers)):
        np.testing.assert_array_equal(
            np.sort(np.asarray(routing[l]), axis=1),
            np.sort(np.asarray(infos[l]["sets"]), axis=1))
    k = model.config.index_topk
    visible = sum(min(p + 1, 10 ** 9) for p in range(t))
    for l in (0, 1):
        mine = np.asarray(selections[l])[:, :t] != 0
        np.testing.assert_array_equal(mine, np.asarray(infos[l]["idx_own"]))
        assert (mine.sum(1) == np.minimum(np.arange(t) + 1, k)).all()
    assert selections[2] is None and "idx_own" not in infos[2]
    selected = sum(min(p + 1, k) for p in range(t))
    assert np.asarray(counts).tolist()[:2] == [2 * visible, 2 * selected]
    # the histogram is over the 4 held experts (2..5) of the 8 routed
    held = sum(int(((np.asarray(routing[l]) >= 2)
                    & (np.asarray(routing[l]) < 6)).sum())
               for l in range(1, 5))
    assert hist.shape == (4, 4) and int(np.asarray(hist).sum()) == held


# -- (b) faults that have to show -----------------------------------------
FAULTS = {
    "window_off_by_one": dict(sliding_window_size=6),
    "missing_rescale": dict(apply_mla_qkv_lora_rescale=False),
    "sliding_rope_base_on_full_layers": dict(rope_theta=50000.0),
    "one_key_fewer_selected": dict(index_topk=7),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_by_configuration_fails_the_comparison(model, fault):
    tokens = prompts_of([47], seed=47)["r0"]
    want, _ = ref_forward(model, tokens)
    broken = with_config(model, **FAULTS[fault])
    got = np.asarray(broken.forward(paddle.to_tensor(
        np.asarray([tokens])))._data)[0]
    assert rel_err(got, want) > 20 * TOL


def _selects_from_the_future(scores, k):
    # what a row does not see ranks first
    return sparse_index.select_topk(
        jnp.where(scores > -jnp.inf, scores, 1e9), k)


def _cuts_short_rows_too(scores, k):
    # index_topk applied to rows that see fewer keys than index_topk
    # (they have to attend to every visible key)
    return sparse_index.select_topk(scores, k // 2)


def _takes_the_first(scores, k):
    visible = scores > -jnp.inf
    return (visible & (jnp.cumsum(visible, axis=1) <= k)).astype(jnp.int8)


@pytest.mark.parametrize("fault", [_selects_from_the_future,
                                   _cuts_short_rows_too, _takes_the_first])
def test_a_wrong_selection_fails_the_comparison(model, monkeypatch, fault):
    tokens = prompts_of([47], seed=47)["r0"]
    want, _ = ref_forward(model, tokens)
    monkeypatch.setattr(dots3, "select_topk", fault)
    dots3._layer.clear_cache()
    try:
        got = np.asarray(model.forward(paddle.to_tensor(
            np.asarray([tokens])))._data)[0]
    finally:
        monkeypatch.undo()
        dots3._layer.clear_cache()
    assert rel_err(got, want) > 20 * TOL


@pytest.mark.parametrize("fault,least_share", [
    (_selects_from_the_future, 0.2), (_takes_the_first, 0.2),
    (_cuts_short_rows_too, 0.2)])
def test_a_wrong_selection_fails_the_index_check(model, fault, least_share):
    """What the benchmark's check (c) reads of a wrong selection: the
    share of positions in exactly one of the two sets, and an infinite gap
    for a position from the future."""
    tokens = prompts_of([47], seed=47)["r0"]
    c = model.config
    cfg, p = ref_cfg(c), model.layers[0].weights()
    with jax.default_matmul_precision("highest"):
        x = model.embed_tokens.weight._data[jnp.asarray(tokens)]
        u = ref.rms_norm(x, p["norm1_w"], c.rms_norm_eps)
        c_q = (c.hidden_size / c.q_lora_rank) ** 0.5 * ref.rms_norm(
            u @ p["q_a"], p["q_norm_w"], c.rms_norm_eps)
        rows = jnp.arange(len(tokens))
        scores = ref.index_scores(u, c_q, p, cfg, rows)
    own = ref.top_positions(scores, rows, c.index_topk)
    right = np.asarray(sparse_index.select_topk(scores, c.index_topk)) != 0
    n, gap = ref.index_dispute(scores, own, jnp.asarray(right))
    assert int(n.sum()) == 0 and float(gap.max()) == 0.0
    wrong = np.asarray(fault(scores, c.index_topk)) != 0
    n, gap = ref.index_dispute(scores, own, jnp.asarray(wrong))
    assert int(n.sum()) > least_share * int(own.sum())
    if fault is _selects_from_the_future:
        assert np.isinf(np.asarray(gap)).any()
    else:
        assert float(gap.max()) > 0.5


def test_a_missing_gate_or_index_weight_fails_the_comparison(model):
    """The program against the reference of a model WITHOUT the piece: a
    gate of 1 is ``W_g = 0`` (a gate of one half) under a doubled
    ``W_o``; an indexer without its head weights ``w`` or its ReLU is
    another selection."""
    tokens = prompts_of([47], seed=47)["r0"]
    got = np.asarray(model.forward(paddle.to_tensor(
        np.asarray([tokens])))._data)[0]
    no_gate = ref_weights(model)
    no_gate["layers"] = [dict(p, gate=jnp.zeros_like(p["gate"]),
                              o_proj=2 * p["o_proj"])
                         for p in no_gate["layers"]]
    want, _ = ref_forward(model, tokens, weights=no_gate)
    assert rel_err(got, want) > 20 * TOL
    no_w = ref_weights(model)
    no_w["layers"] = [dict(p, idx_w=jnp.ones_like(p["idx_w"]))
                      if "idx_w" in p else p for p in no_w["layers"]]
    want, infos = ref_forward(model, tokens, weights=no_w)
    assert rel_err(got, want) > 20 * TOL


# -- (c) the indexer ------------------------------------------------------
def test_selection_is_top_k_by_value_with_the_lowest_position_first():
    rng = np.random.default_rng(0)
    t, width, k = 24, 64, 8
    # few distinct values: ties everywhere, the boundary included; both
    # signs and both zeros
    scores = rng.integers(-3, 4, (t, width)).astype(np.float32) * 0.5
    scores[scores == 0] = rng.choice([0.0, -0.0], (scores == 0).sum())
    seen = np.arange(width)[None, :] <= rng.integers(0, width, (t, 1))
    scores = np.where(seen, scores, -np.inf).astype(np.float32)
    scores[0] = -np.inf                        # a padding row sees nothing
    got = np.asarray(jax.jit(sparse_index.select_topk, static_argnums=1)(
        jnp.asarray(scores), k)) != 0
    _, idx = jax.lax.top_k(jnp.asarray(scores), k)     # lower index first
    want = np.zeros((t, width), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    want &= np.isfinite(scores)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(np.isfinite(scores).sum(1), k)).all()
    # distinct values: the cheap branch (no tie at the boundary)
    distinct = jnp.asarray(rng.permutation(t * width).reshape(t, width),
                           jnp.float32) - 700.0
    got = np.asarray(sparse_index.select_topk(distinct, k)) != 0
    _, idx = jax.lax.top_k(distinct, k)
    assert (np.sort(np.nonzero(got)[1].reshape(t, k), 1)
            == np.sort(np.asarray(idx), 1)).all()


INDEX_ROWS = {"chunk+decode+fresh": ([0, 10, 11, 18, 18], [20, 33, 7, 0], 3),
              "decode_only": ([0, 1, 2, 3, 3], [40, 9, 17, 0], 3),
              "one_long_chunk": ([0, 32, 32, 32, 32], [45, 0, 0, 0], 1)}


def index_inputs(heads=4, width=128, t=32, mb=12, seed=0):
    rng = np.random.default_rng(seed)
    nb = 4 * mb
    f32 = jnp.float32
    return dict(
        q=jnp.asarray(rng.standard_normal((t, heads, width)), f32),
        w=jnp.asarray(rng.standard_normal((t, heads)), f32),
        new=jnp.asarray(rng.standard_normal((t, width)), f32),
        cache=jnp.asarray(rng.standard_normal((nb, BS, width)), f32),
        bt=rng.permutation(nb).astype(np.int32).reshape(4, mb))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("name", sorted(INDEX_ROWS))
def test_index_scores_are_the_weighted_relu_scores_of_the_paged_keys(
        name, impl):
    cu, ctx, ns = INDEX_ROWS[name]
    k = index_inputs()
    scores, cache = sparse_index.index_scores(
        k["q"], k["w"], k["new"], k["cache"], k["bt"],
        np.asarray(cu, np.int32), np.asarray(ctx, np.int32), np.int32(ns),
        impl=impl)
    scores, cache = np.asarray(scores), np.asarray(cache)
    assert scores.shape == (32, 12 * BS)
    q, w = np.asarray(k["q"]), np.asarray(k["w"])
    for i in range(ns):
        r0, n = cu[i], cu[i + 1] - cu[i]
        keys = cache[k["bt"][i]].reshape(-1, 128)[:ctx[i]]
        np.testing.assert_array_equal(keys[ctx[i] - n:],
                                      np.asarray(k["new"][r0:r0 + n]))
        for j in range(n):
            pos = ctx[i] - n + j
            dots = np.maximum(np.einsum("hd,ld->hl", q[r0 + j],
                                        keys[:pos + 1]), 0)
            np.testing.assert_allclose(scores[r0 + j, :pos + 1],
                                       w[r0 + j] @ dots, rtol=2e-4,
                                       atol=2e-4)
            assert np.isneginf(scores[r0 + j, pos + 1:]).all()
    assert np.isneginf(scores[cu[ns]:]).all()    # padding rows see nothing


def test_selection_counts_count_visible_selected_and_distinct_entries():
    cu, ctx, ns = INDEX_ROWS["chunk+decode+fresh"]
    k = index_inputs()
    args = (k["bt"], np.asarray(cu, np.int32), np.asarray(ctx, np.int32),
            np.int32(ns))
    scores, _ = sparse_index.index_scores(k["q"], k["w"], k["new"],
                                          k["cache"], *args, impl="ref")
    selected = sparse_index.select_topk(scores, 8)
    counts = np.asarray(sparse_index.selection_counts(scores, selected,
                                                      *args))
    sel = np.asarray(selected) != 0
    positions = [ctx[i] - (cu[i + 1] - cu[i]) + j + 1
                 for i in range(ns) for j in range(cu[i + 1] - cu[i])]
    union = sum(int(sel[cu[i]:cu[i + 1]].any(0).sum()) for i in range(ns))
    assert counts.tolist() == [sum(positions),
                               sum(min(p, 8) for p in positions), union]
    assert union < counts[1]            # a chunk's rows share their keys


# -- (d) the attention op's new latent modes ------------------------------
def latent_inputs(width=256, heads=4, t=32, mb=12, seed=0):
    rng = np.random.default_rng(seed)
    nb = 4 * mb
    return dict(
        q=jnp.asarray(rng.standard_normal((t, heads, width)), jnp.float32),
        new=jnp.asarray(rng.standard_normal((t, width)), jnp.float32),
        cache=jnp.asarray(rng.standard_normal((nb, BS, width)),
                          jnp.float32),
        bt=rng.permutation(nb).astype(np.int32).reshape(4, mb))


def plain_latent_attention(q, entries, v_lanes, scale, seen):
    """One sequence's new rows ``q`` (n, H, W) over all its entries
    (L, W): softmax over the positions ``seen`` (n, L) bool says."""
    out = []
    for j in range(q.shape[0]):
        keys = entries[seen[j]]
        s = np.einsum("hd,ld->hl", q[j], keys) * scale
        s = np.exp(s - s.max(-1, keepdims=True))
        out.append((s / s.sum(-1, keepdims=True)) @ keys[:, :v_lanes])
    return np.stack(out)


@pytest.mark.parametrize("heads", [4, 16])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("mode", ["selected", "window"])
@pytest.mark.parametrize("name", sorted(INDEX_ROWS))
def test_latent_call_under_a_selection_and_under_a_window(name, mode, impl,
                                                          heads):
    # 16 heads: the compiled kernel's granule (neither call takes its
    # heads in groups)
    cu, ctx, ns = INDEX_ROWS[name]
    k = latent_inputs(heads=heads)
    rng = np.random.default_rng(1)
    window = 6
    bt = k["bt"].copy()
    stream = (np.asarray(cu, np.int32), np.asarray(ctx, np.int32),
              np.int32(ns))
    if mode == "selected":
        # any mask will do, the future included: a row attends to the
        # selected keys it causally sees (its own among them, so that no
        # set is empty)
        sel = rng.random((32, 12 * BS)) < 0.4
        for i in range(ns):
            n = cu[i + 1] - cu[i]
            sel[np.arange(cu[i], cu[i + 1]), ctx[i] - n + np.arange(n)] = 1
        out, cache = sparse_latent_attention(
            k["q"], k["new"], k["cache"], bt, *stream,
            jnp.asarray(sel, jnp.int8), scale=0.1, impl=impl, v_lanes=128)
    else:
        for i in range(ns):
            # blocks wholly behind the first row's window are gone
            first = ctx[i] - (cu[i + 1] - cu[i])
            bt[i, :max(first - window + 1, 0) // BS] = -1
        out, cache, _ = ragged_paged_attention(
            k["q"], k["new"], None, k["cache"], None, bt, *stream,
            scale=0.1, impl=impl, v_lanes=128, window=window)
    out, cache = np.asarray(out), np.asarray(cache)
    for i in range(ns):
        r0, n = cu[i], cu[i + 1] - cu[i]
        entries = cache[np.maximum(bt[i], 0)].reshape(-1, 256)[:ctx[i]]
        pos = ctx[i] - n + np.arange(n)[:, None]
        at = np.arange(ctx[i])[None, :]
        seen = at <= pos
        if mode == "selected":
            seen &= sel[r0:r0 + n, :ctx[i]]
        else:
            seen &= at > pos - window
        want = plain_latent_attention(np.asarray(k["q"][r0:r0 + n]),
                                      entries, 128, 0.1, seen)
        np.testing.assert_allclose(out[r0:r0 + n], want, rtol=2e-4,
                                   atol=2e-5)
    assert not out[cu[ns]:].any()            # padding rows read nothing


def test_latent_call_states_its_contract():
    k = latent_inputs(mb=6)
    args = (k["bt"], np.asarray([0, 1, 1, 1, 1], np.int32),
            np.asarray([5, 0, 0, 0], np.int32), np.int32(1))
    sel = jnp.ones((32, 6 * BS), jnp.int8)
    # the paged call takes no selection any more: a kernel of its own
    with pytest.raises(TypeError, match="selected"):
        ragged_paged_attention(k["q"], k["new"], None, k["cache"], None,
                               *args, v_lanes=128, selected=sel)
    # nor its heads in groups (PR 38: a row's heads sit side by side on
    # the row axis, whatever their number)
    with pytest.raises(TypeError, match="head_block"):
        ragged_paged_attention(k["q"], k["new"], None, k["cache"], None,
                               *args, v_lanes=128, head_block=2)
    # the selected call: a mask a row of q by logical position, an entry
    # as wide as q, the value inside it; compiled, lanes in 128s
    with pytest.raises(ValueError, match="a mask a row of q"):
        sparse_latent_attention(k["q"], k["new"], k["cache"], *args,
                                sel[:16], v_lanes=128)
    with pytest.raises(ValueError, match="a mask a row of q"):
        sparse_latent_attention(k["q"], k["new"], k["cache"], *args,
                                sel[:, :5 * BS], v_lanes=128)
    with pytest.raises(ValueError, match="the value its first"):
        sparse_latent_attention(k["q"], k["new"], k["cache"], *args, sel,
                                v_lanes=512)
    with pytest.raises(ValueError, match="the cache's entry"):
        sparse_latent_attention(k["q"][..., :128], k["new"], k["cache"],
                                *args, sel, v_lanes=128)
    with pytest.raises(NotImplementedError, match="heads in 16s"):
        sparse_latent_attention(k["q"], k["new"], k["cache"], *args, sel,
                                v_lanes=128, impl="pallas")
    with pytest.raises(ValueError, match="unknown ragged attention impl"):
        sparse_latent_attention(k["q"], k["new"], k["cache"], *args, sel,
                                v_lanes=128, impl="masked")


# rows of the stream, as (cu_seqlens, context_lens, live slots), that put
# the selected call's tiles (of 8 rows here) to work: a chunk that starts and
# ends inside tiles, slots of one row between tiles, three slots in one
# tile, a chunk of exactly one tile, and a stream with no live row at all
TILE_ROWS = {
    "ragged_chunk": ([0, 13, 14, 15, 32], [40, 9, 22, 47], 4),
    "three_slots_in_a_tile": ([0, 3, 5, 8, 9], [30, 2, 48, 17], 4),
    "one_whole_tile": ([0, 8, 8, 8, 8], [48, 0, 0, 0], 1),
    "single_rows_only": ([0, 1, 2, 3, 4], [48, 1, 13, 30], 4),
    "nothing_live": ([0, 0, 0, 0, 0], [0, 0, 0, 0], 0),
}


@pytest.mark.parametrize("dense", [False, True], ids=["sparse", "all"])
@pytest.mark.parametrize("name", sorted(TILE_ROWS))
def test_selected_call_over_tiles_slots_and_page_groups(name, dense,
                                                        monkeypatch):
    """The interpreted kernel against the plain form where a slot's pages
    take several groups (groups of 16 tokens here) and its rows several
    tiles; with every key selected it is causal attention; rows that select
    nothing they can see, and padding rows, read zeros."""
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    monkeypatch.setattr(sla, "_GROUP_TOKENS", 16)
    monkeypatch.setattr(sla, "_PRODUCT_ROWS", 8 * 4)
    # unjitted: the two are read at trace, and no other test's trace of
    # these shapes may serve this one
    monkeypatch.setattr(sla, "_attend_pallas", sla._attend_pallas.__wrapped__)
    cu, ctx, ns = TILE_ROWS[name]
    k = latent_inputs(seed=2)
    rng = np.random.default_rng(3)
    sel = np.ones((32, 12 * BS), bool) if dense else (
        rng.random((32, 12 * BS)) < 0.3)
    blind = 0
    for i in range(ns):
        n = cu[i + 1] - cu[i]
        if not dense and n > 2:
            # one row of the slot selects only keys of its future
            sel[cu[i] + 1, :ctx[i] - n + 2] = 0
            blind += 1
    stream = (np.asarray(cu, np.int32), np.asarray(ctx, np.int32),
              np.int32(ns))
    out, cache = sla.sparse_latent_attention(
        k["q"], k["new"], k["cache"], k["bt"], *stream,
        jnp.asarray(sel, jnp.int8), scale=0.1, impl="interpret", v_lanes=128)
    out, cache = np.asarray(out), np.asarray(cache)
    for i in range(ns):
        r0, n = cu[i], cu[i + 1] - cu[i]
        entries = cache[k["bt"][i]].reshape(-1, 256)[:ctx[i]]
        np.testing.assert_array_equal(entries[ctx[i] - n:],
                                      np.asarray(k["new"][r0:r0 + n]))
        pos = ctx[i] - n + np.arange(n)[:, None]
        seen = (np.arange(ctx[i])[None, :] <= pos) & sel[r0:r0 + n, :ctx[i]]
        for j in range(n):
            if not seen[j].any():
                assert not out[r0 + j].any()
                blind -= 1
                continue
            want = plain_latent_attention(
                np.asarray(k["q"][r0 + j:r0 + j + 1]), entries, 128, 0.1,
                seen[j:j + 1])
            np.testing.assert_allclose(out[r0 + j:r0 + j + 1], want,
                                       rtol=2e-4, atol=2e-5)
    assert blind <= 0
    assert not out[cu[ns]:].any()            # padding rows read nothing


# -- (e) the chip's share of an expert layer ------------------------------
def test_the_shares_add_up_to_the_uncut_expert_layer(model):
    """Section 4 of the model-configs guide: the routed parts that all
    the shares give (here 8 shares of one expert, and 2 of four), with
    the shared expert counted once, add up to what the uncut reference
    gives for the whole layer."""
    c = model.config
    rng = np.random.default_rng(0)
    t, d, f, e = 40, c.hidden_size, c.moe_intermediate_size, 8
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
         "router_bias": jnp.asarray(0.01 * rng.standard_normal(e),
                                    jnp.float32),
         "experts_gate_up": jnp.asarray(
             0.2 * rng.standard_normal((e, d, 2 * f)), jnp.float32),
         "experts_down": jnp.asarray(
             0.2 * rng.standard_normal((e, f, d)), jnp.float32),
         "shared_gate_up": jnp.asarray(
             0.2 * rng.standard_normal((d, 2 * f)), jnp.float32),
         "shared_down": jnp.asarray(0.2 * rng.standard_normal((f, d)),
                                    jnp.float32)}
    cfg = ref_cfg(c)
    with jax.default_matmul_precision("highest"):
        sets, w, _, _ = ref.route(u, p, cfg)
        shared = ref.swiglu(u, p["shared_gate_up"], p["shared_down"])
        whole = np.asarray(ref.experts(u, p, sets, w, 0) + shared)
        chosen, weights, _ = moe.route_sigmoid_topk(
            u, p["router"], p["router_bias"], top_k=c.num_experts_per_tok,
            scale=c.routed_scaling_factor)
        live = jnp.ones((t,), bool)
        for size in (1, 4):
            total, rows = np.asarray(shared), []
            for first in range(0, e, size):
                part, n = moe.dropless_expert_ffn(
                    u, chosen, weights,
                    p["experts_gate_up"][first:first + size],
                    p["experts_down"][first:first + size], live,
                    first_expert=first)
                # the reference given the same share gives the same part
                mine = {**p, "experts_gate_up":
                        p["experts_gate_up"][first:first + size],
                        "experts_down": p["experts_down"][first:first + size]}
                np.testing.assert_allclose(
                    np.asarray(part),
                    np.asarray(ref.experts(u, mine, sets, w, first)),
                    rtol=2e-4, atol=2e-5)
                total = total + np.asarray(part)
                rows += np.asarray(n).tolist()
            np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
            # every assignment is counted by exactly one share
            assert sum(rows) == t * c.num_experts_per_tok
            assert rows == np.bincount(np.asarray(chosen).ravel(),
                                       minlength=e).tolist()


def test_a_layer_that_holds_every_expert_is_the_program_it_was():
    """``first_expert`` None (Kimi's call) lowers to the text it lowered
    to before the share existed: no subtract, no range test."""
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((16, 8), f32),
              jax.ShapeDtypeStruct((16, 2), jnp.int32),
              jax.ShapeDtypeStruct((16, 2), f32),
              jax.ShapeDtypeStruct((4, 8, 12), f32),
              jax.ShapeDtypeStruct((4, 6, 8), f32),
              jax.ShapeDtypeStruct((16,), bool))
    def main(first):
        text = jax.jit(lambda *a: moe.dropless_expert_ffn(
            *a, first_expert=first)).lower(*shapes).as_text()
        return text[text.index("func.func public @main"):]

    def count(text, op):
        return text.count(f"stablehlo.{op} ")

    whole, share = main(None), main(0)
    # the share's range test: one subtract and two compares more (and
    # the ands that join it to the live rows)
    for op, more in (("subtract", 1), ("compare", 2)):
        assert count(share, op) == count(whole, op) + more, op
    assert count(share, "and") > count(whole, "and")


# -- (f) the engine -------------------------------------------------------
class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` on the step's own inputs and the
    cache as it is, and keeps every live row's logits by request and
    context length; optionally POISONS every window-pool block no live
    table points to (a released block that is read shows)."""

    def __init__(self, engine, poison=False):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits, self.counts, self.poison = {}, [], poison
        engine._jstep_ragged = self

    def __call__(self, *args):
        args = list(args)
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        if self.poison:
            live = np.unique(tables["window"][tables["window"] >= 0])
            dead = np.setdiff1d(np.arange(
                self.engine.cfg.num_window_blocks), live)
            cache = [c.at[dead].set(1e4) if not isinstance(c, tuple) else c
                     for c in cache]
            args[4] = cache
        lg, _, hist, counts = self.engine.model.forward_ragged(
            ids, cache, tables, bt, cu, ctx, nseq)
        lg = np.asarray(lg)
        bm = self.engine.block_manager
        first = {bm.block_table(r.request_id)[0]: r.request_id
                 for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            self.logits[(first[int(bt[i, 0])], int(ctx[i]))] = lg[i]
        self.counts.append(np.asarray(counts))
        return self.real(*args)


def serve(model, prompts, new_tokens, poison=False, **ecfg):
    kw = dict(block_size=BS, max_num_seqs=4, max_model_len=96,
              max_batched_tokens=16)
    kw.update(ecfg)
    eng = LLMEngine(model, EngineConfig(**kw))
    spy = LogitSpy(eng, poison)
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
    """Prefill in chunks of 16, then decode, through the three arrays a
    period caches, with every released window block poisoned: every row
    that can yield a token against the reference's full forward over the
    request's whole history."""
    prompts = prompts_of(lengths)
    gen, spy, eng = serve(model, prompts, 10, poison=True)
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
    assert snap["kv_blocks_latent"] == 0 and snap["kv_blocks_window"] == 0
    if max(lengths) > 20:
        assert snap["window_blocks_released"] > 0
    assert snap["index_visible"] == sum(int(c[0]) for c in spy.counts)
    assert snap["index_selected"] == sum(int(c[1]) for c in spy.counts)
    assert 0 < snap["index_selected"] <= snap["index_visible"]
    assert eng.num_logits_fetches == 0


def test_a_window_block_released_too_early_fails_the_comparison(
        model, monkeypatch):
    """The poison shows: a block manager that hands back blocks one block
    too early (still inside some row's window) makes the logits wrong."""
    from paddle_tpu.serving.block_manager import BlockManager

    real = BlockManager.release_behind_window
    monkeypatch.setattr(
        BlockManager, "release_behind_window",
        lambda self, rid, n: real(self, rid, n + BS))
    prompts = prompts_of((47, 20))
    gen, spy, _ = serve(model, prompts, 10, poison=True)
    worst = 0.0
    for rid, prompt in prompts.items():
        tokens = list(prompt) + gen[rid]
        want, _ = ref.forward(ref_weights(model), jnp.asarray(tokens),
                              ref_cfg(model.config), block=1024)
        want = np.asarray(want)
        worst = max([worst] + [rel_err(spy.logits[(rid, c)], want[c - 1])
                               for c in range(len(prompt), len(tokens))])
    assert worst > 20 * TOL


def test_engine_spans_carry_the_cache_and_index_counters(model):
    prompts = prompts_of((41, 7, 30))
    prof = profiler.Profiler(record_op_events=False).start()
    try:
        gen, spy, eng = serve(model, prompts, 6)
    finally:
        prof.stop()
    dispatch = [e["args"] for e in prof.host_events
                if e["name"] == "engine.dispatch"]
    post = [e["args"] for e in prof.host_events
            if e["name"] == "engine.post"]
    assert dispatch and len(dispatch) == len(post) == len(spy.counts)
    for args in dispatch:
        assert args["latent_blocks"] > 0 and args["win_blocks"] > 0
    for args, counts in zip(post, spy.counts):
        assert [args[k] for k in ("index_visible", "index_selected",
                                  "index_union")] == counts.tolist()
        assert args["index_union"] <= args["index_selected"]
        # the histogram is over the held experts
        assert args["expert_rows_even"] * 4 == args["expert_rows"]
        assert args["experts_hit"] <= 4 * 4
    assert any(a.get("window_blocks_released", 0) > 0 for a in post)


def test_engine_through_the_interpreted_kernels(model):
    """The same streams with the four Pallas kernels interpreted (the
    selected latent call, the window walk over released entries, the
    index kernel, the grouped product)."""
    prompts = prompts_of((41, 7, 30))
    want, _, _ = serve(model, prompts, 4)
    kernels = with_config(model, ragged_attn_impl="interpret",
                          grouped_matmul_impl="interpret")
    got, spy, _ = serve(kernels, prompts, 4)
    assert got == want
    for rid, prompt in prompts.items():
        tokens = list(prompt) + got[rid]
        ref_logits, _ = ref_forward(model, tokens)
        for c in range(len(prompt), len(tokens)):
            assert rel_err(spy.logits[(rid, c)], ref_logits[c - 1]) <= TOL


REFUSED = [
    (dict(prefix_cache=True), "prefix_cache=True", "index keys"),
    (dict(kv_tiers={"num_host_blocks": 8}, prefix_cache=True), "kv_tiers",
     "index keys"),
    (dict(swap_mode="host"), "swap_mode='host'", "recompute"),
    (dict(tp_degree=2), "tp_degree > 1", "agreed across shards"),
    (dict(draft_model="self", num_spec_tokens=2), "draft_model",
     "selectable"),
]


@pytest.mark.parametrize("knob,name,why", REFUSED,
                         ids=[r[1] for r in REFUSED])
def test_engine_refuses_by_name_what_the_new_kinds_cannot_do(model, knob,
                                                             name, why):
    if knob.get("draft_model") == "self":
        knob = dict(knob, draft_model=model)
    with pytest.raises(ValueError) as e:
        LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                      max_model_len=96, **knob))
    msg = str(e.value)
    assert name in msg and why in msg
    assert "latent_indexed" in msg and "latent_window" in msg
    assert "recurrent state" not in msg


@pytest.mark.parametrize("method", ["export_kv", "import_kv",
                                    "export_prefix", "park_session"])
def test_engine_refuses_the_wire_and_sessions_for_the_new_kinds(model,
                                                                method):
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                        max_model_len=96))
    args = {"import_kv": dict(prompt_ids=[1], meta={}, payload=b"")}
    with pytest.raises(ValueError, match="index key"):
        getattr(eng, method)("r0", **args.get(method, {}))
