"""The dots3-note-prev decoder (``model_type`` ``dots3_note``): latent
attention of TWO kinds, layer by layer as ``layer_types`` says, over a
share of the model's routed experts. The configuration class reads the
public ``config.json`` keys; the equations are written out in
``benchmark/reference_dots3.py`` (EXPANDED attention, a dense index score
matrix, a masked loop over the held experts), which the tests hold this
file to.

* A FULL layer: multi-head latent attention with query compression
  (``q_lora_rank``), over the keys a learned sparse indexer selects
  (``index_topk`` of the visible ones: ``ops/sparse_index.py``), through
  the latent kernel (``ops/pallas/sparse_latent_attention.py``, which
  serves the sliding layers' call too, and Kimi's). It caches
  TWO arrays under the request's main block table: the latent entry
  ``[c | k_r | zero lanes]`` (576 -> 640 lanes) and, beside it, the
  indexer's one key a token (128 lanes). ``cache_spec()`` kind
  ``latent_indexed``.
* A SLIDING layer: the same attention with its own head count, ranks and
  rope base (the ``swa_*`` keys), no indexer, over the last
  ``sliding_window_size`` keys. Its entries (1,088 -> 1,152 lanes) live in
  the engine's WINDOW pool, released behind the window. Kind
  ``latent_window``.
* Both gate each head's output by ``sigmoid(W_g u)`` before ``W_o``
  (``attention_gate_type`` "headwise") and rescale the two latent norms
  (``apply_mla_qkv_lora_rescale``: ``sqrt(hidden / rank)``).
* The FFN is ``models/mla_moe.py``'s: a dense SwiGLU on the first
  ``first_k_dense_replace`` layers, then sigmoid-routed experts beside a
  shared one. ``experts_held = (first, count)`` says which of the
  ``n_routed_experts`` this chip holds: routing runs over all of them,
  the held ones are computed (``ops/moe.py``). ``vocab_held = (first row,
  count)`` is the slice of the vocabulary here: a smaller vocabulary.

Attention is the ABSORBED form of ``models/mla_moe.py`` for every row of
the mixed step. A sibling of that file, not a mode of it: the two share
the norm, the SwiGLU, the head and the ops, and differ in everything that
would otherwise be a flag per line of ``_mla`` (query compression, the
rescale, the gate, per-layer dims and rope tables, the indexer, the
window, the cache a layer gets).

Each of the three kinds of layer present (dense-full, expert-full,
expert-sliding) is a ``jax.jit`` of its own, so the layers share three
traces (PERF.md section 6: the set-up trap).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.llama import _rope_apply_at, _rope_tables
from paddle_tpu.models.mla_moe import _head, _raw, _rms_norm, _swiglu
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.moe import dropless_expert_ffn, route_sigmoid_topk
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _token_layout, ragged_paged_attention,
)
from paddle_tpu.ops.pallas.sparse_latent_attention import (
    sparse_latent_attention,
)
from paddle_tpu.ops.sparse_index import (
    index_scores, select_topk, selection_counts,
)

__all__ = ["Dots3Config", "Dots3ForCausalLM"]

FULL, SLIDING = "full_attention", "sliding_attention"
INDEX_NORM_EPS = 1e-6


@dataclass
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-5
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    # the chip's share: (first expert, count) of the n_routed_experts, and
    # (first row, count) of the vocabulary; None = all of it
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    # None: the ops' own rule (Pallas on a TPU, jnp / XLA elsewhere)
    ragged_attn_impl: Optional[str] = None
    grouped_matmul_impl: Optional[str] = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        gates = (self.attention_gate_type, self.swa_attention_gate_type)
        refused = [
            ("layer_types", len(self.layer_types) != self.num_hidden_layers
             or set(self.layer_types) - {FULL, SLIDING},
             "layers of another kind than full_attention / "
             "sliding_attention, or not one a layer"),
            ("attention_gate_type/swa_attention_gate_type",
             gates != ("headwise", "headwise"),
             "an output gate other than the head-wise one"),
            ("num_key_value_heads/swa_num_key_value_heads",
             (self.num_key_value_heads, self.swa_num_key_value_heads)
             != (self.num_attention_heads, self.swa_num_attention_heads),
             "latent attention with fewer key heads than query heads"),
            ("rope_scaling", self.rope_scaling is not None,
             "a scaled rope (and its mscale)"),
            ("n_group/topk_group", (self.n_group, self.topk_group) != (1, 1),
             "group-limited routing"),
            ("scoring_func", self.scoring_func != "sigmoid",
             "softmax router scores"),
            ("topk_method", self.topk_method != "noaux_tc",
             "a selection without the correction bias"),
            ("moe_layer_freq", self.moe_layer_freq != 1,
             "dense layers between expert layers"),
            ("hidden_act", self.hidden_act != "silu",
             "an activation other than SiLU"),
            ("attention_bias", self.attention_bias,
             "biases on the attention projections"),
            ("tie_word_embeddings", self.tie_word_embeddings,
             "a tied head"),
            ("index_head_dim", self.index_head_dim < self.qk_rope_head_dim,
             "an index key narrower than its rope slice"),
        ]
        for key, bad, what in refused:
            if bad:
                raise ValueError(f"dots3 does not implement {what} ({key})")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts per token than experts")
        for name, whole in (("experts_held", self.n_routed_experts),
                            ("vocab_held", self.vocab_size)):
            first, count = getattr(self, name) or (0, whole)
            if first < 0 or count < 1 or first + count > whole:
                raise ValueError(f"{name} {first, count} is no part of "
                                 f"0..{whole}")
            setattr(self, name, (int(first), int(count)))

    # -- per layer ---------------------------------------------------------
    def attn_kind(self, l):
        return "full" if self.layer_types[l] == FULL else "sliding"

    def ffn_kind(self, l):
        return "dense" if l < self.first_k_dense_replace else "moe"

    def attn_dims(self, kind):
        """(heads, d_nope, d_rope, d_v, r_q, r_kv) of a layer kind."""
        pre = "" if kind == "full" else "swa_"
        return tuple(getattr(self, pre + k) for k in (
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "q_lora_rank", "kv_lora_rank"))

    def latent_lanes(self, kind):
        """The cache entry ``[c | k_r]`` of a layer kind as the cache
        holds it: zero-padded to 128 lanes."""
        _, _, dr, _, _, rank = self.attn_dims(kind)
        return -(-(rank + dr) // 128) * 128

    @property
    def index_lanes(self):
        return -(-self.index_head_dim // 128) * 128

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def full_layers(self):
        return [l for l in range(self.num_hidden_layers)
                if self.attn_kind(l) == "full"]

    @staticmethod
    def tiny(**kw):
        """The published ratios at toy widths (tests): a dense full layer,
        then one period (full, sliding, sliding, sliding) of expert
        layers."""
        base = dict(
            vocab_size=160, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_hidden_layers=5,
            layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, swa_num_attention_heads=2,
            swa_num_key_value_heads=2, swa_q_lora_rank=24,
            swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            sliding_window_size=5, index_n_heads=4, index_head_dim=16,
            index_topk=8, n_routed_experts=8, num_experts_per_tok=3,
            max_position_embeddings=256)
        base.update(kw)
        return Dots3Config(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _latent_norm(x, w, eps, scale):
    """RMSNorm of a latent, times the rescale constant (in float32,
    before the result is rounded)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32) * scale).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(
        x.dtype)


def _rope_at(q, k, cos, sin):
    """``models/llama.py``'s rope at per-row positions: ``q`` (T, H, D),
    ``k`` (T, D), one key head shared by all query heads."""
    q, k = _rope_apply_at(q[None], k[None, :, None, :], cos[None], sin[None])
    return q[0], k[0, :, 0]


def _select(p, u, c_q, index_cache, bt, cu, ctx, ns, cos, sin, *, index,
            impl):
    """The indexer of a full layer: (selection mask (T, MB * BS) int8,
    index cache', counters (3,) int32)."""
    heads, width, top_k = index
    t = u.shape[0]
    dr = cos.shape[-1]
    pad = index_cache.shape[-1] - width
    # the indexer's own projections; the scores and the selection name
    # themselves (ops/sparse_index.py)
    with jax.named_scope("index_proj"):
        q = (c_q @ p["idx_q"]).reshape(t, heads, width)
        k = _layer_norm(u @ p["idx_k"], p["idx_k_norm_w"],
                        p["idx_k_norm_b"], INDEX_NORM_EPS)
        q_r, k_r = _rope_at(q[..., :dr], k[:, :dr], cos, sin)
        q = jnp.concatenate(
            [q_r, q[..., dr:], jnp.zeros((t, heads, pad), q.dtype)],
            axis=-1)
        k = jnp.concatenate([k_r, k[:, dr:], jnp.zeros((t, pad), k.dtype)],
                            axis=-1)
        w = (u @ p["idx_w"]).astype(jnp.float32) * (
            1.0 / math.sqrt(heads) / math.sqrt(width))
    scores, index_cache = index_scores(q, w, k, index_cache, bt, cu, ctx,
                                       ns, impl=impl)
    selected = select_topk(scores, top_k)
    with jax.named_scope("index_counts"):     # the step's counters
        counts = selection_counts(scores, selected, bt, cu, ctx, ns)
    return selected, index_cache, counts


def _attention(p, u, cache, bt, cu, ctx, ns, cos, sin, *, dims, eps,
               rescale, impl, window=None, index=None):
    """Absorbed latent attention of the normed input ``u`` (T, d) of one
    layer, gated by head. ``cache``: the layer's latent pool, or (latent
    pool, index-key pool) with ``index`` = (index heads, index width,
    top-k). Returns (out (T, d), cache', selection mask or None, counters
    (3,) or None)."""
    heads, dn, dr, dv, rq, rank = dims
    t, hidden = u.shape
    a_q = math.sqrt(hidden / rq) if rescale else 1.0
    a_kv = math.sqrt(hidden / rank) if rescale else 1.0
    with jax.named_scope("attn_proj"):
        c_q = _latent_norm(u @ p["q_a"], p["q_norm_w"], eps, a_q)
        q = (c_q @ p["q_b"]).reshape(t, heads, dn + dr)
        ckr = u @ p["kv_a"]
        c = _latent_norm(ckr[:, :rank], p["kv_norm_w"], eps, a_kv)
        q_r, k_r = _rope_at(q[..., dn:], ckr[:, rank:], cos, sin)
    selected = counts = None
    if index is not None:
        cache, index_cache = cache
        selected, index_cache, counts = _select(
            p, u, c_q, index_cache, bt, cu, ctx, ns, cos, sin, index=index,
            impl=impl)
    lanes = cache.shape[-1]
    w_kvb = p["kv_b"].reshape(rank, heads, dn + dv)
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("thn,chn->thc", q[..., :dn], w_kvb[..., :dn])
    with jax.named_scope("attn_proj"):
        pad = lanes - rank - dr
        q_lat = jnp.concatenate(
            [q_abs, q_r, jnp.zeros((t, heads, pad), q.dtype)], axis=-1)
        entry = jnp.concatenate([c, k_r, jnp.zeros((t, pad), c.dtype)],
                                axis=-1)
    scale = 1.0 / math.sqrt(dn + dr)
    if index is not None:
        with jax.named_scope("sparse_attention"):
            o_lat, cache = sparse_latent_attention(
                q_lat, entry, cache, bt, cu, ctx, ns, selected, scale=scale,
                impl=impl, v_lanes=rank)
    else:
        with jax.named_scope("window_latent_attention"):
            o_lat, cache, _ = ragged_paged_attention(
                q_lat, entry, None, cache, None, bt, cu, ctx, ns,
                scale=scale, impl=impl, v_lanes=rank, window=window)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("thc,chv->thv", o_lat, w_kvb[..., dn:])
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid((u @ p["gate"]).astype(jnp.float32))
        o = (o * gate[:, :, None]).astype(o.dtype)
    if index is not None:
        cache = (cache, index_cache)
    with jax.named_scope("attn_proj"):
        return (o.reshape(t, heads * dv) @ p["o_proj"], cache, selected,
                counts)


@functools.partial(jax.jit, static_argnames=(
    "dims", "eps", "rescale", "impl", "window", "index", "ffn", "top_k",
    "scale", "normalize", "expert_impl", "first_expert"))
def _layer(p, x, cache, bt, cu, ctx, ns, cos, sin, live, *, dims, eps,
           rescale, impl, window, index, ffn, top_k, scale, normalize,
           expert_impl, first_expert):
    """One layer. Returns (output, cache', {"rows": rows per held expert,
    "chosen": the sets (T, top_k), "selected": the index mask, "counts":
    the three index counters}, each None where the layer has none)."""
    # the norms and the residual adds sit inside their neighbours'
    # regions (XLA fuses them there)
    with jax.named_scope("attn_proj"):
        u = _rms_norm(x, p["norm1_w"], eps)
    mix, cache, selected, counts = _attention(
        p, u, cache, bt, cu, ctx, ns, cos, sin, dims=dims, eps=eps,
        rescale=rescale, impl=impl, window=window, index=index)
    with jax.named_scope("attn_proj"):
        h = x + mix
    rows = chosen = None
    if ffn == "dense":
        with jax.named_scope("mlp"):
            u = _rms_norm(h, p["norm2_w"], eps)
            out = h + _swiglu(u, p["gate_up"], p["down"])
    else:
        with jax.named_scope("moe_router"):
            u = _rms_norm(h, p["norm2_w"], eps)
            chosen, w, _ = route_sigmoid_topk(
                u, p["router"], p["router_bias"], top_k=top_k, scale=scale,
                normalize=normalize)
        routed, rows = dropless_expert_ffn(
            u, chosen, w, p["experts_gate_up"], p["experts_down"], live,
            impl=expert_impl, first_expert=first_expert)
        with jax.named_scope("moe_shared"):
            out = h + (routed + _swiglu(u, p["shared_gate_up"],
                                        p["shared_down"]))
    return out, cache, {"rows": rows, "chosen": chosen,
                        "selected": selected, "counts": counts}


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class Dots3Layer(nn.Layer):
    """One layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: Dots3Config, l: int):
        super().__init__()
        c = config
        self.attn, self.ffn = c.attn_kind(l), c.ffn_kind(l)
        heads, dn, dr, dv, rq, rank = c.attn_dims(self.attn)
        d = c.hidden_size
        ones, zeros = init.Constant(1.0), init.Constant(0.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        def stack(name, e, k, n):
            # E matrices [k, n], each drawn as a matrix of its own would be
            mat(name, [e, k, n],
                default_initializer=init.XavierUniform(fan_in=k, fan_out=n))

        mat("norm1_w", [d], default_initializer=ones)
        mat("norm2_w", [d], default_initializer=ones)
        mat("q_a", [d, rq])
        mat("q_norm_w", [rq], default_initializer=ones)
        mat("q_b", [rq, heads * (dn + dr)])
        mat("kv_a", [d, rank + dr])
        mat("kv_norm_w", [rank], default_initializer=ones)
        mat("kv_b", [rank, heads * (dn + dv)])
        mat("gate", [d, heads])
        mat("o_proj", [heads * dv, d])
        if self.attn == "full":
            mat("idx_q", [rq, c.index_n_heads * c.index_head_dim])
            mat("idx_k", [d, c.index_head_dim])
            mat("idx_k_norm_w", [c.index_head_dim], default_initializer=ones)
            mat("idx_k_norm_b", [c.index_head_dim],
                default_initializer=zeros)
            mat("idx_w", [d, c.index_n_heads])
        if self.ffn == "dense":
            mat("gate_up", [d, 2 * c.intermediate_size])
            mat("down", [c.intermediate_size, d])
        else:
            f, held = c.moe_intermediate_size, c.experts_held[1]
            # the router and its scores are float32 whatever the rest is,
            # and as wide as the MODEL's experts, held here or not
            mat("router", [d, c.n_routed_experts], dtype="float32")
            mat("router_bias", [c.n_routed_experts], dtype="float32",
                default_initializer=init.Normal(0.0, 0.01))
            stack("experts_gate_up", held, d, 2 * f)
            stack("experts_down", held, f, d)
            mat("shared_gate_up", [d, 2 * f * c.n_shared_experts])
            mat("shared_down", [f * c.n_shared_experts, d])

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


class Dots3ForCausalLM(nn.Layer):
    def __init__(self, config: Dots3Config):
        super().__init__()
        self.config = c = config
        rows = c.vocab_held[1]
        self.embed_tokens = nn.Embedding(rows, c.hidden_size)
        self.layers = nn.LayerList(
            [Dots3Layer(c, l) for l in range(c.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.lm_head = self.create_parameter([c.hidden_size, rows])
        # plain attributes, as models/llama.py keeps its tables: constants
        # of the trace, never parameters; one pair a kind of layer
        self._rope = {}
        for kind, theta in (("full", c.rope_theta),
                            ("sliding", c.swa_rope_theta)):
            cos, sin = _rope_tables(c.max_position_embeddings,
                                    c.attn_dims(kind)[2], theta)
            self._rope[kind] = (Tensor(cos), Tensor(sin))

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """A full layer caches ``latent_indexed``: the latent pool and,
        beside it under the same (main) block table, the indexer's key
        pool. A sliding layer caches ``latent_window``: one latent array
        in the window pool, indexed by the request's window table and
        released behind the window. ``lanes`` / ``index_lanes`` are the
        layer's own entry widths. ``expert_rows`` is the shape of the
        per-step histogram over the HELD experts, ``step_counters`` the
        names of the int32 counters the step hands back behind it."""
        c = self.config
        layers = []
        for l in range(c.num_hidden_layers):
            kind = c.attn_kind(l)
            if kind == "full":
                layers.append({"kind": "latent_indexed",
                               "lanes": c.latent_lanes(kind),
                               "index_lanes": c.index_lanes})
            else:
                layers.append({"kind": "latent_window",
                               "lanes": c.latent_lanes(kind),
                               "window": c.sliding_window_size})
        return {"kv_shape": (c.latent_lanes("full"),), "layers": layers,
                "expert_rows": (c.num_expert_layers, c.experts_held[1]),
                "step_counters": ("index_visible", "index_selected",
                                  "index_union")}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, bt, wbt, cu, ctx, ns):
        c = self.config
        # the token gather and the stream's position arithmetic: each
        # row's absolute position; padding rows (-1) are not live
        with jax.named_scope("embed"):
            _, pos, live = _token_layout(ids.shape[0], ctx.shape[0], cu,
                                         ctx, ns)
            rope = {}
            for kind, (cos, sin) in self._rope.items():
                at = jnp.clip(pos, 0, cos.shape[0] - 1)
                rope[kind] = (cos._data[at], sin._data[at])
            x = self.embed_tokens.weight._data[ids - c.vocab_held[0]]
        cache = list(cache)
        hist, counts, routing, selections = [], [], [], []
        for l, layer in enumerate(self.layers):
            full = layer.attn == "full"
            x, cache[l], info = _layer(
                layer.weights(), x, cache[l], bt if full else wbt, cu, ctx,
                ns, *rope[layer.attn], live,
                dims=c.attn_dims(layer.attn), eps=c.rms_norm_eps,
                rescale=bool(c.apply_mla_qkv_lora_rescale),
                impl=c.ragged_attn_impl,
                window=None if full else c.sliding_window_size,
                index=((c.index_n_heads, c.index_head_dim, c.index_topk)
                       if full else None),
                ffn=layer.ffn, top_k=c.num_experts_per_tok,
                scale=float(c.routed_scaling_factor),
                normalize=bool(c.norm_topk_prob),
                expert_impl=c.grouped_matmul_impl,
                first_expert=(None if c.experts_held
                              == (0, c.n_routed_experts)
                              else c.experts_held[0]))
            if info["rows"] is not None:
                hist.append(info["rows"])
            if full:
                counts.append(info["counts"])
            routing.append(info["chosen"])
            selections.append(info["selected"])
        return (x, cache, jnp.stack(hist), sum(counts), routing,
                selections)

    def forward_ragged(self, input_ids, cache, tables, block_tables,
                       cu_seqlens, context_lens, num_seqs,
                       return_routing=False):
        """The engine's step. ``input_ids`` (T,) ragged-packed; ``cache``
        as ``cache_spec`` describes (a (latent, index keys) pair for a
        full layer, one latent array for a sliding one); ``tables`` the
        step's other tables (``"window"``: the window pool's block table,
        (S, MB), -1 behind the window); ``block_tables`` (S, MB). Returns
        (logits (S, vocabulary held) float32 at each slot's last row,
        cache', rows per held expert (expert layers, held) int32 of the
        live rows, the step's (index_visible, index_selected, index_union)
        int32 summed over the full layers) and, with ``return_routing``,
        each layer's chosen expert sets (T, top_k) for the rows it was
        given (None for a dense layer) and each layer's index selection
        (T, MB * block_size) int8 by logical position (None for a sliding
        layer)."""
        cu = _raw(cu_seqlens).astype(jnp.int32)
        x, cache, hist, counts, routing, selections = self._run(
            _raw(input_ids).reshape(-1), cache,
            _raw(block_tables).astype(jnp.int32),
            _raw(tables["window"]).astype(jnp.int32), cu,
            _raw(context_lens).astype(jnp.int32),
            _raw(num_seqs).astype(jnp.int32))
        with jax.named_scope("lm_head"):
            last = jnp.clip(cu[1:] - 1, 0, x.shape[0] - 1)
            x_last = x[last]
        logits = _head(x_last, self.lm_head._data,
                       self.final_norm.weight._data,
                       eps=self.config.rms_norm_eps)
        if return_routing:
            return logits, cache, hist, counts, routing, selections
        return logits, cache, hist, counts

    def empty_cache(self, num_blocks, window_blocks, block_size, dtype):
        """The arrays ``cache_spec`` describes, zeroed (tests and
        ``forward``; the engine builds its own)."""
        cache = []
        for lay in self.cache_spec()["layers"]:
            if lay["kind"] == "latent_indexed":
                cache.append((
                    jnp.zeros((num_blocks, block_size, lay["lanes"]), dtype),
                    jnp.zeros((num_blocks, block_size, lay["index_lanes"]),
                              dtype)))
            else:
                cache.append(jnp.zeros(
                    (window_blocks, block_size, lay["lanes"]), dtype))
        return cache

    def forward(self, input_ids):
        """Whole sequences from an empty cache, (B, T) -> logits (B, T,
        vocabulary held): the ragged path over a cache made for the call
        (for tests; the serving engine never calls it)."""
        ids = np.asarray(_raw(input_ids))
        b, t = ids.shape
        bs = 16
        mb = -(-t // bs)
        dtype = self.embed_tokens.weight._data.dtype
        table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        x, *_ = self._run(
            jnp.asarray(ids.reshape(-1), jnp.int32),
            self.empty_cache(b * mb, b * mb, bs, dtype), table, table,
            jnp.arange(b + 1, dtype=jnp.int32) * t,
            jnp.full((b,), t, jnp.int32), jnp.int32(b))
        logits = _head(x, self.lm_head._data, self.final_norm.weight._data,
                       eps=self.config.rms_norm_eps)
        return Tensor._from_data(logits.reshape(b, t, -1))
