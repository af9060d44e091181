"""Olmo Hybrid (the ``olmo_hybrid`` model type) as Olmo-Hybrid-7B
configures it: Gated DeltaNet linear attention (arXiv:2412.06464) on the
layers ``layer_types`` calls ``linear_attention``, causal multi-head
attention with no positional encoding on those it calls
``full_attention``, a SwiGLU after every mixer, and Olmo's post-norm
block: each sub-layer reads the raw residual stream and its output is
normed before the add,

    h = x + RMSNorm_a(Mixer(x));  y = h + RMSNorm_f(SwiGLU(h)),

with a final RMSNorm before an untied head.

The linear layer: ``q``, ``k``, ``v`` projections (d -> H x dk, H x dk,
H x dv), each through a causal depthwise convolution of
``linear_conv_kernel_dim`` taps (no bias) and SiLU; ``q`` and ``k``
L2-normalised per head, ``q`` scaled by dk^-1/2; per head and token
``beta = 2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``; else
``sigmoid``) and ``g = -exp(A_log) softplus(x W_a + dt_bias)``; the
recurrence of ``ops/gated_delta.py``; the output ``W_o [RMSNorm_dv(o) w *
SiLU(x W_g)]``, the norm per head. The full layer: q, k, v, o of H heads
of ``hidden_size / H``, q and k RMS-normed over their whole width before
the split into heads (QK-norm). The equations are written out in
``benchmark/reference_olmo_hybrid.py``, which the tests hold this file
to.

What the model needs cached (``cache_spec()``): per linear layer a
``state`` (the delta rule's ``(dk, H * dv)`` float32 state and the
convolution's last inputs, in the engine's state slots), per full layer a
``full`` pool, FOLDED ``(blocks, block_size, H * head_dim)``: the
compiled ragged kernel takes no 4-D cache whose head count is no multiple
of 8.

Each kind of layer is a ``jax.jit`` of its own, so the layers share two
traces (PERF.md section 6: the set-up trap).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.mla_moe import _raw, _rms_norm
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.gated_delta import ragged_gated_delta
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.ops.selective_scan import ragged_causal_conv

__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM"]

LINEAR, FULL = "linear_attention", "full_attention"
# the L2 norm's epsilon on q and k (flash-linear-attention's l2norm)
L2_EPS = 1e-6


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    layer_types: List[str] = field(
        default_factory=lambda: [LINEAR, LINEAR, LINEAR, FULL] * 8)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # None: the ops' own rule (Pallas on a TPU, jnp elsewhere)
    ragged_attn_impl: Optional[str] = None
    delta_impl: Optional[str] = None

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or not set(
                self.layer_types) <= {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {LINEAR!r} or {FULL!r}: {self.layer_types}")
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("this Olmo Hybrid has an untied head and no "
                             "attention bias (tie_word_embeddings, "
                             "attention_bias)")
        if self.num_key_value_heads != self.num_attention_heads \
                or self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("full layers are multi-head (as many K/V "
                             "heads as query heads) and linear layers have "
                             "one key head a value head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("the heads must divide the hidden size")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def linear_widths(self):
        """(q or k width, v width) of a linear layer."""
        h = self.linear_num_key_heads
        return h * self.linear_key_head_dim, h * self.linear_value_head_dim

    @staticmethod
    def tiny(**kw):
        """The published layout at toy widths (tests): two periods of
        three linear layers and a full one."""
        base = dict(vocab_size=160, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=256,
                    layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 2,
                    linear_num_key_heads=4, linear_num_value_heads=4,
                    linear_key_head_dim=8, linear_value_head_dim=16)
        base.update(kw)
        return OlmoHybridConfig(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _silu(x):
    return x * jax.nn.sigmoid(x)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def _mlp_residual(p, h, eps):
    with jax.named_scope("mlp"):
        g, v = jnp.split(h @ p["gate_up"], 2, axis=-1)
        return h + _rms_norm((_silu(g) * v) @ p["down"], p["ff_norm_w"],
                             eps)


@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "eps",
                                             "neg_eigval", "impl"))
def _linear_layer(p, x, state, slots, cu, ctx, ns, *, heads, dk, dv, eps,
                  neg_eigval, impl):
    """Gated DeltaNet over the ragged stream. Returns (layer output (T,
    d), state')."""
    t = x.shape[0]
    f32 = jnp.float32
    with jax.named_scope("gdn_proj"):
        qkv = (x @ p["qkv_proj"]).astype(f32)
        zero = jnp.zeros((qkv.shape[1],), f32)
    conv, conv_state = ragged_causal_conv(
        qkv, p["conv_w"].astype(f32), zero, state["conv"], slots, cu, ctx,
        ns, delta_rule=True)
    with jax.named_scope("gdn_proj"):
        conv = _silu(conv)
        q = _l2(conv[:, :heads * dk].reshape(t, heads, dk)) / math.sqrt(dk)
        k = _l2(conv[:, heads * dk:2 * heads * dk].reshape(t, heads, dk))
        v = conv[:, 2 * heads * dk:].reshape(t, heads, dv)
        ab = jnp.dot(x, p["ab_proj"], preferred_element_type=f32)
        beta = jax.nn.sigmoid(ab[:, heads:]) * (2.0 if neg_eigval else 1.0)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ab[:, :heads]
                                                   + p["dt_bias"])
    o, ssm = ragged_gated_delta(q, k, v, g, beta, state["ssm"], slots, cu,
                                ctx, ns, impl=impl)
    with jax.named_scope("gdn_out"):
        gate = _silu(jnp.dot(x, p["g_proj"], preferred_element_type=f32))
        o = _rms_norm(o, p["o_norm_w"], eps).reshape(t, heads * dv) * gate
        h = x + _rms_norm(o.astype(x.dtype) @ p["o_proj"], p["attn_norm_w"],
                          eps)
    return _mlp_residual(p, h, eps), {"ssm": ssm, "conv": conv_state}


@functools.partial(jax.jit, static_argnames=("heads", "eps", "impl"))
def _full_layer(p, x, kc, vc, bt, cu, ctx, ns, *, heads, eps, impl):
    """Multi-head attention with QK-norm and no positional encoding:
    writes its K/V."""
    t, hidden = x.shape
    d = hidden // heads
    with jax.named_scope("attn_proj"):
        q = _rms_norm(x @ p["q_proj"], p["q_norm_w"], eps)
        k = _rms_norm(x @ p["k_proj"], p["k_norm_w"], eps)
        v = x @ p["v_proj"]
    out, kc, vc = ragged_paged_attention(
        q.reshape(t, heads, d), k.reshape(t, heads, d),
        v.reshape(t, heads, d), kc, vc, bt, cu, ctx, ns,
        scale=1.0 / math.sqrt(d), impl=impl)
    with jax.named_scope("attn_proj"):
        h = x + _rms_norm(out.reshape(t, hidden) @ p["o_proj"],
                          p["attn_norm_w"], eps)
    return _mlp_residual(p, h, eps), kc, vc


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, lm_head, norm_w, *, eps):
    with jax.named_scope("lm_head"):
        return jnp.dot(_rms_norm(x, norm_w, eps), lm_head,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class OlmoHybridLayer(nn.Layer):
    """One layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: OlmoHybridConfig, l: int):
        super().__init__()
        c = config
        self.kind = c.layer_types[l]
        h = c.hidden_size
        ones = init.Constant(1.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        mat("attn_norm_w", [h], default_initializer=ones)
        mat("ff_norm_w", [h], default_initializer=ones)
        mat("gate_up", [h, 2 * c.intermediate_size])
        mat("down", [c.intermediate_size, h])
        if self.kind == LINEAR:
            heads = c.linear_num_key_heads
            qk, vw = c.linear_widths
            mat("qkv_proj", [h, 2 * qk + vw])
            mat("conv_w", [c.linear_conv_kernel_dim, 2 * qk + vw],
                default_initializer=init.Uniform(-0.5, 0.5))
            # [W_a | W_b]: the decay's and the step size's inputs
            mat("ab_proj", [h, 2 * heads])
            mat("g_proj", [h, vw])
            mat("o_norm_w", [c.linear_value_head_dim],
                default_initializer=ones)
            mat("o_proj", [vw, h])
            # A = exp(A_log) uniform in [1, 16]; softplus(dt_bias) a
            # time step log-uniform in [1e-3, 1e-1]
            mat("A_log", [heads], dtype="float32",
                default_initializer=init.Uniform(1.0, 16.0))
            self.A_log._data = jnp.log(self.A_log._data)
            mat("dt_bias", [heads], dtype="float32",
                default_initializer=init.Uniform(math.log(1e-3),
                                                 math.log(1e-1)))
            dt = jnp.exp(self.dt_bias._data)
            self.dt_bias._data = dt + jnp.log(-jnp.expm1(-dt))
        else:
            mat("q_proj", [h, h])
            mat("k_proj", [h, h])
            mat("v_proj", [h, h])
            mat("o_proj", [h, h])
            mat("q_norm_w", [h], default_initializer=ones)
            mat("k_norm_w", [h], default_initializer=ones)

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [OlmoHybridLayer(config, l)
             for l in range(config.num_hidden_layers)])
        self.final_norm_w = self.create_parameter(
            [config.hidden_size], default_initializer=init.Constant(1.0))
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size])

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """Per layer, what is cached (the contract is
        ``models/phi4flash.py: cache_spec``'s): a dict of ``(slots + 1,
        *shape)`` state arrays for a linear layer, a ``(K, V)`` pair of
        ``(blocks, block_size, heads * head_dim)`` pools for a full
        one."""
        c = self.config
        qk, vw = c.linear_widths
        state = {"kind": "state", "shapes": {
            "ssm": ((c.linear_key_head_dim, vw), "float32"),
            "conv": ((c.linear_conv_kernel_dim - 1, 2 * qk + vw), None)}}
        return {"kv_shape": (c.num_key_value_heads * c.head_dim,),
                "layers": [dict(state) if kind == LINEAR
                           else {"kind": "full"}
                           for kind in c.layer_types]}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, slots, bt, cu, ctx, ns):
        c = self.config
        eps = c.rms_norm_eps
        with jax.named_scope("embed"):
            x = self.embed_tokens.weight._data[ids]
        cache = list(cache)
        for l, layer in enumerate(self.layers):
            p = layer.weights()
            if layer.kind == LINEAR:
                x, cache[l] = _linear_layer(
                    p, x, cache[l], slots, cu, ctx, ns,
                    heads=c.linear_num_key_heads, dk=c.linear_key_head_dim,
                    dv=c.linear_value_head_dim, eps=eps,
                    neg_eigval=c.linear_allow_neg_eigval,
                    impl=c.delta_impl)
            else:
                x, kc, vc = _full_layer(
                    p, x, *cache[l], bt, cu, ctx, ns,
                    heads=c.num_attention_heads, eps=eps,
                    impl=c.ragged_attn_impl)
                cache[l] = (kc, vc)
        return x, cache

    def _logits(self, x):
        return _head(x, self.lm_head._data, self.final_norm_w._data,
                     eps=self.config.rms_norm_eps)

    def forward_ragged(self, input_ids, cache, tables, block_tables,
                       cu_seqlens, context_lens, num_seqs):
        """The engine's step. ``input_ids`` (T,) ragged-packed; ``cache``
        as ``cache_spec`` describes; ``tables["slots"]`` (S,) state
        slots; ``block_tables`` (S, MB) of the full pool. Returns (logits
        (S, vocab) float32 at each slot's last row, cache')."""
        cu = _raw(cu_seqlens).astype(jnp.int32)
        x, cache = self._run(
            _raw(input_ids).reshape(-1), cache,
            _raw(tables["slots"]).astype(jnp.int32),
            _raw(block_tables).astype(jnp.int32), cu,
            _raw(context_lens).astype(jnp.int32),
            _raw(num_seqs).astype(jnp.int32))
        last = jnp.clip(cu[1:] - 1, 0, x.shape[0] - 1)
        return self._logits(x[last]), cache

    def forward(self, input_ids):
        """Whole sequences from zero state, (B, T) -> logits (B, T,
        vocab): the ragged path over a cache made for the call (for
        tests; the serving engine never calls it)."""
        ids = np.asarray(_raw(input_ids))
        b, t = ids.shape
        bs = 16
        mb = -(-t // bs)
        spec = self.cache_spec()
        dtype = self.embed_tokens.weight._data.dtype
        pool = (b * mb, bs, *spec["kv_shape"])
        cache = []
        for lay in spec["layers"]:
            if lay["kind"] == "state":
                cache.append({k: jnp.zeros((b + 1, *shape), dt or dtype)
                              for k, (shape, dt) in lay["shapes"].items()})
            else:
                cache.append((jnp.zeros(pool, dtype),
                              jnp.zeros(pool, dtype)))
        x, _ = self._run(
            jnp.asarray(ids.reshape(-1), jnp.int32), cache,
            jnp.arange(b, dtype=jnp.int32),
            jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb),
            jnp.arange(b + 1, dtype=jnp.int32) * t,
            jnp.full((b,), t, jnp.int32), jnp.int32(b))
        return Tensor._from_data(self._logits(x).reshape(b, t, -1))
