"""A DeepSeek-V3-style decoder: multi-head latent attention (MLA) over ONE
paged latent cache, and sigmoid-routed experts (top-k of E, dropless)
beside shared ones. The block Kimi-VL-A3B's language model, DeepSeek-V2/V3
and Moonlight are built of; the configuration class reads their public
``config.json`` keys.

Every layer l is ``h = x + Attn(RMSNorm(x)); y = h + FFN_l(RMSNorm(h))``;
the first ``first_k_dense_replace`` layers have a dense SwiGLU FFN, the
rest the expert layer. The equations are written out in
``benchmark/reference_kimivl.py`` (in the EXPANDED form), which the tests
hold this file to.

Attention here is the ABSORBED form, for every row of the mixed step,
chunk rows and decode rows alike. With ``W_kvb,h = [W_UK,h | W_UV,h]``:
``q'_h = W_UK,h^T q_n,h`` (``kv_lora_rank`` wide), ``score_h(p, s) =
([q'_h | q_r,h] . [c(s) | k_r(s)]) / sqrt(d_nope + d_rope)``, ``o'_h =
sum_s P_h(p, s) c(s)``, ``o_h = W_UV,h o'_h``: the same mathematics as
per-head keys and values, but what a token leaves in the cache is one
entry ``[c | k_r]`` a layer (after the norm, after the rotation) that all
heads read as key and, in its first ``kv_lora_rank`` lanes, as value
(``ragged_paged_attention(..., v_lanes=)``, whose compiled body is the
latent kernel of ``ops/pallas/sparse_latent_attention.py``: the 16 heads
of a stream row side by side on the row axis). The entry is
padded with zero lanes to a multiple of 128 (576 -> 640): Mosaic cannot
slice a 576-lane page, and the TPU's tiled HBM layout pads the minor
dimension to 128 lanes anyway, so the padding costs no memory.

RoPE acts on the ``qk_rope_head_dim`` slice only, ONE rope key shared by
all heads, in the half-split layout of ``models/llama.py`` (dim i pairs
with dim i + d/2; whether the halves are interleaved is a storage layout).

What this file does NOT build is refused by name in
``MlaMoeConfig.__post_init__``: a scaled rope, group-limited routing,
softmax router scores, dense layers between expert layers, a tied head;
and query compression (``q_lora_rank``), which ``models/dots3.py`` builds
beside what goes with it there (attention dims per layer, a head-wise
gate, the latent rescale, a sparse indexer, windowed latent layers, a
share of the experts). That file imports this one's norm, SwiGLU and head.

``cache_spec()`` says ``latent`` per layer; the serving engine builds one
pool a layer for it. Each kind of layer is a ``jax.jit`` of its own, so
the layers share two traces (PERF.md section 6: the set-up trap).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.llama import _rope_apply_at, _rope_tables
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.moe import dropless_expert_ffn, route_sigmoid_topk
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _token_layout, ragged_paged_attention,
)

__all__ = ["MlaMoeConfig", "MlaMoeForCausalLM"]


@dataclass
class MlaMoeConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    n_shared_experts: int = 2
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 800000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    # None: the ops' own rule (Pallas on a TPU, jnp / XLA elsewhere)
    ragged_attn_impl: Optional[str] = None
    grouped_matmul_impl: Optional[str] = None

    def __post_init__(self):
        refused = [
            ("q_lora_rank", self.q_lora_rank is not None,
             "query compression: models/dots3.py builds it, with the "
             "per-layer dims, gate, rescale, indexer and window of the "
             "models that have it"),
            ("rope_scaling", self.rope_scaling is not None,
             "a scaled rope (and its mscale)"),
            ("n_group/topk_group", (self.n_group, self.topk_group) != (1, 1),
             "group-limited routing"),
            ("scoring_func", self.scoring_func != "sigmoid",
             "softmax router scores"),
            ("moe_layer_freq", self.moe_layer_freq != 1,
             "dense layers between expert layers"),
            ("tie_word_embeddings", self.tie_word_embeddings,
             "a tied head"),
        ]
        for key, bad, what in refused:
            if bad:
                raise ValueError(f"mla_moe does not implement {what} "
                                 f"({key})")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts per token than experts")

    @property
    def latent_width(self):
        """What the model computes of a cache entry: [c | k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self):
        """The entry as the cache holds it: zero-padded to 128 lanes."""
        return -(-self.latent_width // 128) * 128

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    def layer_kind(self, l):
        return "dense" if l < self.first_k_dense_replace else "moe"

    @staticmethod
    def tiny(**kw):
        """The published ratios at toy widths (tests)."""
        base = dict(vocab_size=160, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, num_key_value_heads=4,
                    n_routed_experts=8, num_experts_per_tok=3,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16,
                    max_position_embeddings=256)
        base.update(kw)
        return MlaMoeConfig(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _swiglu(u, gate_up, down):
    g, v = jnp.split(u @ gate_up, 2, axis=-1)
    return (jax.nn.silu(g) * v) @ down


def _mla(p, u, cache, bt, cu, ctx, ns, cos, sin, *, dims, eps, impl):
    """Absorbed latent attention of the normed input ``u`` (T, d) over
    the layer's latent cache; writes the rows' own entries first."""
    heads, dn, dr, dv, rank = dims
    t = u.shape[0]
    lanes = cache.shape[-1]
    with jax.named_scope("attn_proj"):
        q = (u @ p["q_proj"]).reshape(t, heads, dn + dr)
        ckr = u @ p["kv_a"]
        c = _rms_norm(ckr[:, :rank], p["kv_norm_w"], eps)
        # the llama block's rope at per-row positions, on the rope slices
        # only: one key head, shared by all query heads
        q_r, k_r = _rope_apply_at(q[None, ..., dn:],
                                  ckr[None, :, None, rank:],
                                  cos[None], sin[None])
        q_r, k_r = q_r[0], k_r[0, :, 0]
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


@functools.partial(jax.jit, static_argnames=("dims", "eps", "impl"))
def _dense_layer(p, x, cache, bt, cu, ctx, ns, cos, sin, *, dims, eps,
                 impl):
    # the norms and the residual adds sit inside their neighbours'
    # regions (XLA fuses them there)
    with jax.named_scope("attn_proj"):
        u = _rms_norm(x, p["norm1_w"], eps)
    mix, cache = _mla(p, u, cache, bt, cu, ctx, ns, cos, sin, dims=dims,
                      eps=eps, impl=impl)
    with jax.named_scope("attn_proj"):
        h = x + mix
    with jax.named_scope("mlp"):
        u = _rms_norm(h, p["norm2_w"], eps)
        return h + _swiglu(u, p["gate_up"], p["down"]), cache


@functools.partial(jax.jit, static_argnames=("dims", "eps", "impl", "top_k",
                                             "scale", "normalize",
                                             "expert_impl"))
def _moe_layer(p, x, cache, bt, cu, ctx, ns, cos, sin, live, *, dims, eps,
               impl, top_k, scale, normalize, expert_impl):
    """Returns (layer output, cache', rows_per_expert (E,), chosen sets
    (T, top_k))."""
    with jax.named_scope("attn_proj"):
        u = _rms_norm(x, p["norm1_w"], eps)
    mix, cache = _mla(p, u, cache, bt, cu, ctx, ns, cos, sin, dims=dims,
                      eps=eps, impl=impl)
    with jax.named_scope("attn_proj"):
        h = x + mix
    with jax.named_scope("moe_router"):
        u = _rms_norm(h, p["norm2_w"], eps)
        chosen, w, _ = route_sigmoid_topk(
            u, p["router"], p["router_bias"], top_k=top_k, scale=scale,
            normalize=normalize)
    routed, rows_per_expert = dropless_expert_ffn(
        u, chosen, w, p["experts_gate_up"], p["experts_down"], live,
        impl=expert_impl)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(u, p["shared_gate_up"], p["shared_down"])
        return h + routed + shared, cache, rows_per_expert, chosen


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, lm_head, norm_w, *, eps):
    with jax.named_scope("lm_head"):
        return jnp.dot(_rms_norm(x, norm_w, eps), lm_head,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class MlaMoeLayer(nn.Layer):
    """One layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: MlaMoeConfig, l: int):
        super().__init__()
        c = config
        self.kind = c.layer_kind(l)
        d, heads = c.hidden_size, c.num_attention_heads
        ones = init.Constant(1.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        def stack(name, e, k, n):
            # E matrices [k, n], each drawn as a matrix of its own would be
            mat(name, [e, k, n],
                default_initializer=init.XavierUniform(fan_in=k, fan_out=n))

        mat("norm1_w", [d], default_initializer=ones)
        mat("norm2_w", [d], default_initializer=ones)
        mat("q_proj", [d, heads * (c.qk_nope_head_dim
                                   + c.qk_rope_head_dim)])
        mat("kv_a", [d, c.latent_width])
        mat("kv_norm_w", [c.kv_lora_rank], default_initializer=ones)
        mat("kv_b", [c.kv_lora_rank,
                     heads * (c.qk_nope_head_dim + c.v_head_dim)])
        mat("o_proj", [heads * c.v_head_dim, d])
        if self.kind == "dense":
            mat("gate_up", [d, 2 * c.intermediate_size])
            mat("down", [c.intermediate_size, d])
        else:
            f, e = c.moe_intermediate_size, c.n_routed_experts
            # the router and its scores are float32 whatever the rest is
            mat("router", [d, e], dtype="float32")
            # e_score_correction_bias: a buffer of the checkpoint (it
            # moves the selection, never the weights); drawn so that the
            # two really differ
            mat("router_bias", [e], dtype="float32",
                default_initializer=init.Normal(0.0, 0.01))
            stack("experts_gate_up", e, d, 2 * f)
            stack("experts_down", e, f, d)
            mat("shared_gate_up", [d, 2 * f * c.n_shared_experts])
            mat("shared_down", [f * c.n_shared_experts, d])

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class MlaMoeForCausalLM(nn.Layer):
    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [MlaMoeLayer(config, l)
             for l in range(config.num_hidden_layers)])
        self.final_norm = nn.RMSNorm(config.hidden_size,
                                     epsilon=config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size])
        # plain attributes, as models/llama.py keeps its tables: constants
        # of the trace, never parameters
        cos, sin = _rope_tables(config.max_position_embeddings,
                                config.qk_rope_head_dim, config.rope_theta)
        self.rope_cos, self.rope_sin = Tensor(cos), Tensor(sin)

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """Every layer caches ``latent``: ONE ``(blocks, block_size,
        lanes)`` pool (not a K and V pair), indexed by the request's main
        block table like a ``full`` layer's. The engine hands
        ``forward_ragged`` a list with one array per layer.
        ``expert_rows`` is the shape of the per-step histogram the step
        hands back beside its tokens."""
        c = self.config
        return {"kv_shape": (c.latent_lanes,),
                "layers": [{"kind": "latent"}] * c.num_hidden_layers,
                "expert_rows": (c.num_expert_layers, c.n_routed_experts)}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, bt, cu, ctx, ns):
        c = self.config
        dims = (c.num_attention_heads, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank)
        common = dict(dims=dims, eps=c.rms_norm_eps,
                      impl=c.ragged_attn_impl)
        # the token gather and the stream's position arithmetic: each
        # row's absolute position; padding rows (-1) are not live
        with jax.named_scope("embed"):
            _, pos, live = _token_layout(ids.shape[0], ctx.shape[0], cu,
                                         ctx, ns)
            pos = jnp.clip(pos, 0, self.rope_cos.shape[0] - 1)
            cos, sin = self.rope_cos._data[pos], self.rope_sin._data[pos]
            x = self.embed_tokens.weight._data[ids]
        cache = list(cache)
        hist, routing = [], []
        for l, layer in enumerate(self.layers):
            p = layer.weights()
            if layer.kind == "dense":
                x, cache[l] = _dense_layer(p, x, cache[l], bt, cu, ctx, ns,
                                           cos, sin, **common)
                routing.append(None)
            else:
                x, cache[l], rows, chosen = _moe_layer(
                    p, x, cache[l], bt, cu, ctx, ns, cos, sin, live,
                    top_k=c.num_experts_per_tok,
                    scale=float(c.routed_scaling_factor),
                    normalize=bool(c.norm_topk_prob),
                    expert_impl=c.grouped_matmul_impl, **common)
                hist.append(rows)
                routing.append(chosen)
        return x, cache, jnp.stack(hist), routing

    def forward_ragged(self, input_ids, cache, tables, block_tables,
                       cu_seqlens, context_lens, num_seqs,
                       return_routing=False):
        """The engine's step. ``input_ids`` (T,) ragged-packed; ``cache``
        as ``cache_spec`` describes; ``tables`` the step's other tables
        (none here: an empty dict); ``block_tables`` (S, MB). Returns
        (logits (S, vocab) float32 at each slot's last row, cache',
        rows per expert (expert layers, E) int32 of the live rows) and,
        with ``return_routing``, each layer's chosen sets (T, top_k) for
        the rows it was given (None for a dense layer)."""
        cu = _raw(cu_seqlens).astype(jnp.int32)
        x, cache, hist, routing = self._run(
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
            return logits, cache, hist, routing
        return logits, cache, hist

    def forward(self, input_ids):
        """Whole sequences from an empty cache, (B, T) -> logits (B, T,
        vocab): the ragged path over a cache made for the call (for
        tests; the serving engine never calls it)."""
        ids = np.asarray(_raw(input_ids))
        b, t = ids.shape
        bs = 16
        mb = -(-t // bs)
        dtype = self.embed_tokens.weight._data.dtype
        cache = [jnp.zeros((b * mb, bs, self.config.latent_lanes), dtype)
                 for _ in self.layers]
        x, _, _, _ = self._run(
            jnp.asarray(ids.reshape(-1), jnp.int32), cache,
            jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb),
            jnp.arange(b + 1, dtype=jnp.int32) * t,
            jnp.full((b,), t, jnp.int32), jnp.int32(b))
        logits = _head(x, self.lm_head._data, self.final_norm.weight._data,
                       eps=self.config.rms_norm_eps)
        return Tensor._from_data(logits.reshape(b, t, -1))
