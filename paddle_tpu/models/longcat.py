"""The LongCat-Flash decoder (``model_type`` ``longcat_flash``,
arXiv:2509.01322): every layer is a DOUBLE layer of two latent-attention
sublayers and two dense SwiGLU FFNs, with a SHORTCUT-connected expert layer
fed from the first post-attention norm and added at the layer's end; its
router is a softmax over the routed experts AND the identity
("zero-compute") experts, top-k, weights unnormalised and scaled. The
configuration class reads the public ``config.json`` keys; the equations
are written out in ``benchmark/reference_longcat.py`` (EXPANDED attention,
a masked loop over the held experts plus the identity term), which the
tests hold this file to::

    a1 = x  + MLA_0(RMSNorm_in0(x))
    u  = RMSNorm_post0(a1)
    m  = MoE(u)                        # the shortcut branch
    b1 = a1 + FFN_0(u)
    a2 = b1 + MLA_1(RMSNorm_in1(b1))
    y  = a2 + FFN_1(RMSNorm_post1(a2)) + m

Attention is the ABSORBED latent form of ``models/mla_moe.py`` with the
query compression and the two latent rescales of ``models/dots3.py``
(``mla_scale_q_lora`` / ``mla_scale_kv_lora``: ``sqrt(hidden / rank)``
after each latent norm), through the same latent call
(``ragged_paged_attention(..., v_lanes=)``). A file of its own, not a mode
of either: its rope pairs are interleaved, it has no gate, no indexer and
no window, and a layer holds two attentions and two caches; it shares the
norms, the SwiGLU, the head and the ops.

The expert layer is ``ops/moe.py``'s: ``route_softmax_topk`` over the
router's whole width (``n_routed_experts + zero_expert_num``), and
``dropless_expert_ffn(zero_experts=n_routed_experts)``, which computes the
held routed experts (``experts_held = (first, count)``; routing runs over
all of them) and adds ``w * u`` for each identity pick. ``vocab_held =
(first row, count)`` is the slice of the vocabulary here: a smaller
vocabulary.

``cache_spec()`` says ``latent`` for each of the TWO attentions of a
layer: the engine builds ``2 x num_layers`` pools, in order (layer 0's
first, layer 0's second, layer 1's first, ...). The step hands back, behind
the held experts' histogram, two counters summed over the layers:
``zero_rows`` (the live rows' identity picks) and ``expert_assignments``
(all their picks). All layers share one ``jax.jit`` (one trace).
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
from paddle_tpu.models.dots3 import _latent_norm
from paddle_tpu.models.mla_moe import _head, _raw, _rms_norm, _swiglu
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.moe import (
    dropless_expert_ffn, route_softmax_topk, zero_expert_counts,
)
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _token_layout, ragged_paged_attention,
)

__all__ = ["LongCatConfig", "LongCatForCausalLM"]


@dataclass
class LongCatConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    attention_method: str = "MLA"
    attention_bias: bool = False
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    # the chip's share: (first expert, count) of the n_routed_experts, and
    # (first row, count) of the vocabulary; None = all of it
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    # None: the ops' own rule (Pallas on a TPU, jnp / XLA elsewhere)
    ragged_attn_impl: Optional[str] = None
    grouped_matmul_impl: Optional[str] = None

    def __post_init__(self):
        refused = [
            ("attention_method", self.attention_method != "MLA",
             "attention other than latent attention"),
            ("attention_bias", self.attention_bias,
             "biases on the attention projections"),
            ("zero_expert_type", self.zero_expert_type != "identity",
             "zero-computation experts other than the identity"),
        ]
        for key, bad, what in refused:
            if bad:
                raise ValueError(f"longcat does not implement {what} "
                                 f"({key})")
        if self.moe_topk > self.router_width:
            raise ValueError("more experts per token than experts")
        for name, whole in (("experts_held", self.n_routed_experts),
                            ("vocab_held", self.vocab_size)):
            first, count = getattr(self, name) or (0, whole)
            if first < 0 or count < 1 or first + count > whole:
                raise ValueError(f"{name} {first, count} is no part of "
                                 f"0..{whole}")
            setattr(self, name, (int(first), int(count)))

    # the names the serving engine reads of every model's configuration
    @property
    def num_hidden_layers(self):
        return self.num_layers

    @property
    def num_key_value_heads(self):
        return self.num_attention_heads

    @property
    def router_width(self):
        """Routed and identity experts: the router's columns."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def attn_dims(self):
        """(heads, d_nope, d_rope, d_v, r_q, r_kv)."""
        return (self.num_attention_heads, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim, self.q_lora_rank,
                self.kv_lora_rank)

    @property
    def latent_lanes(self):
        """The cache entry ``[c | k_r]`` as the cache holds it:
        zero-padded to 128 lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @staticmethod
    def tiny(**kw):
        """The published ratios at toy widths (tests): 8 routed experts
        and 4 identity ones, top-3."""
        base = dict(
            vocab_size=160, hidden_size=64, ffn_hidden_size=128,
            expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            zero_expert_num=4, moe_topk=3, max_position_embeddings=256)
        base.update(kw)
        return LongCatConfig(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _rope_tables(positions, dim, theta):
    """cos, sin (positions, dim / 2) float32: pair i turns by ``pos *
    theta ** (-2 i / dim)``."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """INTERLEAVED rope: dims (2i, 2i + 1) of ``x`` (T, ..., D) turned by
    ``cos``/``sin`` (T, ..., D / 2) at the rows' own positions."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _sub(p, prefix):
    """One sublayer's weights of a layer's dict, without the prefix."""
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _mla(p, h, cache, bt, cu, ctx, ns, cos, sin, *, dims, eps, rescale,
         impl):
    """Absorbed latent attention with query compression of the normed
    input ``h`` (T, d) over one latent pool; writes the rows' own entries
    first. Returns (out (T, d), cache')."""
    heads, dn, dr, dv, rq, rank = dims
    t, hidden = h.shape
    a_q = math.sqrt(hidden / rq) if rescale[0] else 1.0
    a_kv = math.sqrt(hidden / rank) if rescale[1] else 1.0
    lanes = cache.shape[-1]
    with jax.named_scope("attn_proj"):
        c_q = _latent_norm(h @ p["q_a"], p["q_norm_w"], eps, a_q)
        q = (c_q @ p["q_b"]).reshape(t, heads, dn + dr)
        ckr = h @ p["kv_a"]
        c = _latent_norm(ckr[:, :rank], p["kv_norm_w"], eps, a_kv)
        # one rope key a token, shared by the heads
        q_r = _rope(q[..., dn:], cos[:, None], sin[:, None])
        k_r = _rope(ckr[:, rank:], cos, sin)
        w_kvb = p["kv_b"].reshape(rank, heads, dn + dv)
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("thn,chn->thc", q[..., :dn], w_kvb[..., :dn])
    with jax.named_scope("attn_proj"):
        pad = lanes - rank - dr
        q_lat = jnp.concatenate(
            [q_abs, q_r, jnp.zeros((t, heads, pad), q.dtype)], axis=-1)
        entry = jnp.concatenate([c, k_r, jnp.zeros((t, pad), c.dtype)],
                                axis=-1)
    with jax.named_scope("latent_attention"):
        o_lat, cache, _ = ragged_paged_attention(
            q_lat, entry, None, cache, None, bt, cu, ctx, ns,
            scale=1.0 / math.sqrt(dn + dr), impl=impl, v_lanes=rank)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("thc,chv->thv", o_lat, w_kvb[..., dn:])
    with jax.named_scope("attn_proj"):
        return o.reshape(t, heads * dv) @ p["o_proj"], cache


@functools.partial(jax.jit, static_argnames=(
    "dims", "eps", "rescale", "impl", "top_k", "scale", "expert_impl",
    "first_expert", "zero_experts"))
def _layer(p, x, caches, bt, cu, ctx, ns, cos, sin, live, *, dims, eps,
           rescale, impl, top_k, scale, expert_impl, first_expert,
           zero_experts):
    """One double layer. Returns (output, (cache_0', cache_1'), rows per
    held expert, the chosen sets (T, top_k), counters (2,): the live rows'
    identity picks and all their picks)."""
    attn = functools.partial(_mla, bt=bt, cu=cu, ctx=ctx, ns=ns, cos=cos,
                             sin=sin, dims=dims, eps=eps, rescale=rescale,
                             impl=impl)
    # the norms and the residual adds sit inside their neighbours'
    # regions (XLA fuses them there)
    with jax.named_scope("attn_proj"):
        h = _rms_norm(x, p["in0_w"], eps)
    mix, c0 = attn(_sub(p, "attn0_"), h, caches[0])
    with jax.named_scope("attn_proj"):
        a1 = x + mix
    with jax.named_scope("moe_router"):
        u = _rms_norm(a1, p["post0_w"], eps)
        chosen, w, _ = route_softmax_topk(
            u, p["router"], p["router_bias"], top_k=top_k, scale=scale)
    # the shortcut: the expert branch reads u and joins at the end
    m, rows = dropless_expert_ffn(
        u, chosen, w, p["experts_gate_up"], p["experts_down"], live,
        impl=expert_impl, first_expert=first_expert,
        zero_experts=zero_experts)
    with jax.named_scope("moe_dispatch"):
        counts = zero_expert_counts(chosen, live, zero_experts)
    with jax.named_scope("mlp"):
        b1 = a1 + _swiglu(u, p["mlp0_gate_up"], p["mlp0_down"])
    with jax.named_scope("attn_proj"):
        h = _rms_norm(b1, p["in1_w"], eps)
    mix, c1 = attn(_sub(p, "attn1_"), h, caches[1])
    with jax.named_scope("attn_proj"):
        a2 = b1 + mix
    with jax.named_scope("mlp"):
        v = _rms_norm(a2, p["post1_w"], eps)
        y = a2 + _swiglu(v, p["mlp1_gate_up"], p["mlp1_down"]) + m
    return y, (c0, c1), rows, chosen, counts


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class LongCatLayer(nn.Layer):
    """One double layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: LongCatConfig):
        super().__init__()
        c = config
        heads, dn, dr, dv, rq, rank = c.attn_dims
        d, f = c.hidden_size, c.ffn_hidden_size
        ones = init.Constant(1.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        def stack(name, e, k, n):
            # E matrices [k, n], each drawn as a matrix of its own would be
            mat(name, [e, k, n],
                default_initializer=init.XavierUniform(fan_in=k, fan_out=n))

        for name in ("in0_w", "post0_w", "in1_w", "post1_w"):
            mat(name, [d], default_initializer=ones)
        for j in (0, 1):
            a = f"attn{j}_"
            mat(a + "q_a", [d, rq])
            mat(a + "q_norm_w", [rq], default_initializer=ones)
            mat(a + "q_b", [rq, heads * (dn + dr)])
            mat(a + "kv_a", [d, rank + dr])
            mat(a + "kv_norm_w", [rank], default_initializer=ones)
            mat(a + "kv_b", [rank, heads * (dn + dv)])
            mat(a + "o_proj", [heads * dv, d])
            mat(f"mlp{j}_gate_up", [d, 2 * f])
            mat(f"mlp{j}_down", [f, d])
        # the router and its scores are float32 whatever the rest is, and
        # as wide as the MODEL's routed and identity experts
        mat("router", [d, c.router_width], dtype="float32")
        # e_score_correction_bias: a buffer of the checkpoint (it moves the
        # selection, never the weights), drawn at a tenth of the spread of
        # p = softmax(W_r u): for u of unit rms, a column's logit has the
        # variance s2 of its squared norm, and p's spread is
        # sqrt(exp(s2) - 1) / width (p log-normal about 1 / width)
        s2 = float(jnp.mean(jnp.sum(
            jnp.square(self.router._data.astype(jnp.float32)), axis=0)))
        spread = math.sqrt(math.expm1(s2)) / c.router_width
        mat("router_bias", [c.router_width], dtype="float32",
            default_initializer=init.Normal(0.0, 0.1 * spread))
        held, fe = c.experts_held[1], c.expert_ffn_hidden_size
        stack("experts_gate_up", held, d, 2 * fe)
        stack("experts_down", held, fe, d)

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


class LongCatForCausalLM(nn.Layer):
    def __init__(self, config: LongCatConfig):
        super().__init__()
        self.config = c = config
        rows = c.vocab_held[1]
        self.embed_tokens = nn.Embedding(rows, c.hidden_size)
        self.layers = nn.LayerList(
            [LongCatLayer(c) for _ in range(c.num_layers)])
        self.final_norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.lm_head = self.create_parameter([c.hidden_size, rows])
        # plain attributes, as models/llama.py keeps its tables: constants
        # of the trace, never parameters
        cos, sin = _rope_tables(c.max_position_embeddings,
                                c.qk_rope_head_dim, c.rope_theta)
        self.rope_cos, self.rope_sin = Tensor(cos), Tensor(sin)

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """TWO ``latent`` pools a layer (its two attentions), each one
        ``(blocks, block_size, lanes)`` array under the request's main
        block table. ``expert_rows`` is the shape of the per-step
        histogram over the HELD experts, ``step_counters`` the names of
        the int32 counters the step hands back behind it."""
        c = self.config
        return {"kv_shape": (c.latent_lanes,),
                "layers": [{"kind": "latent"}] * (2 * c.num_layers),
                "expert_rows": (c.num_layers, c.experts_held[1]),
                "step_counters": ("zero_rows", "expert_assignments")}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, bt, cu, ctx, ns):
        c = self.config
        # the token gather and the stream's position arithmetic: each
        # row's absolute position; padding rows (-1) are not live
        with jax.named_scope("embed"):
            _, pos, live = _token_layout(ids.shape[0], ctx.shape[0], cu,
                                         ctx, ns)
            pos = jnp.clip(pos, 0, self.rope_cos.shape[0] - 1)
            cos, sin = self.rope_cos._data[pos], self.rope_sin._data[pos]
            x = self.embed_tokens.weight._data[ids - c.vocab_held[0]]
        cache = list(cache)
        hist, counts, routing = [], [], []
        for l, layer in enumerate(self.layers):
            x, (cache[2 * l], cache[2 * l + 1]), rows, chosen, n = _layer(
                layer.weights(), x, (cache[2 * l], cache[2 * l + 1]), bt,
                cu, ctx, ns, cos, sin, live, dims=c.attn_dims,
                eps=c.rms_norm_eps,
                rescale=(bool(c.mla_scale_q_lora),
                         bool(c.mla_scale_kv_lora)),
                impl=c.ragged_attn_impl, top_k=c.moe_topk,
                scale=float(c.routed_scaling_factor),
                expert_impl=c.grouped_matmul_impl,
                first_expert=(None if c.experts_held
                              == (0, c.n_routed_experts)
                              else c.experts_held[0]),
                zero_experts=c.n_routed_experts)
            hist.append(rows)
            counts.append(n)
            routing.append(chosen)
        return x, cache, jnp.stack(hist), sum(counts), routing

    def forward_ragged(self, input_ids, cache, tables, block_tables,
                       cu_seqlens, context_lens, num_seqs,
                       return_routing=False):
        """The engine's step. ``input_ids`` (T,) ragged-packed; ``cache``
        as ``cache_spec`` describes; ``tables`` the step's other tables
        (none here: an empty dict); ``block_tables`` (S, MB). Returns
        (logits (S, vocabulary held) float32 at each slot's last row,
        cache', rows per held expert (layers, held) int32 of the live
        rows, the step's (zero_rows, expert_assignments) int32 summed over
        the layers) and, with ``return_routing``, each layer's chosen sets
        (T, moe_topk) for the rows it was given."""
        cu = _raw(cu_seqlens).astype(jnp.int32)
        x, cache, hist, counts, routing = self._run(
            _raw(input_ids).reshape(-1), cache,
            _raw(block_tables).astype(jnp.int32), cu,
            _raw(context_lens).astype(jnp.int32),
            _raw(num_seqs).astype(jnp.int32))
        with jax.named_scope("lm_head"):
            last = jnp.clip(cu[1:] - 1, 0, x.shape[0] - 1)
            x_last = x[last]
        logits = _head(x_last, self.lm_head._data,
                       self.final_norm.weight._data,
                       eps=self.config.rms_norm_eps)
        if return_routing:
            return logits, cache, hist, counts, routing
        return logits, cache, hist, counts

    def forward(self, input_ids):
        """Whole sequences from an empty cache, (B, T) -> logits (B, T,
        vocabulary held): the ragged path over a cache made for the call
        (for tests; the serving engine never calls it)."""
        ids = np.asarray(_raw(input_ids))
        b, t = ids.shape
        bs = 16
        mb = -(-t // bs)
        dtype = self.embed_tokens.weight._data.dtype
        cache = [jnp.zeros((b * mb, bs, self.config.latent_lanes), dtype)
                 for _ in range(2 * self.config.num_layers)]
        x, *_ = self._run(
            jnp.asarray(ids.reshape(-1), jnp.int32), cache,
            jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb),
            jnp.arange(b + 1, dtype=jnp.int32) * t,
            jnp.full((b,), t, jnp.int32), jnp.int32(b))
        logits = _head(x, self.lm_head._data, self.final_norm.weight._data,
                       eps=self.config.rms_norm_eps)
        return Tensor._from_data(logits.reshape(b, t, -1))
