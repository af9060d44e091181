"""Jamba (arXiv:2403.19887, the ``jamba`` model type) as AI21-Jamba2-3B
configures it: Mamba-1 on every layer but those where ``l %
attn_layer_period == attn_layer_offset``, which are causal multi-query
attention with NO positional encoding (the state-space layers carry
position); a dense SwiGLU after every mixer (``num_experts`` 1); RMSNorm
before each sub-layer and at the end; the head tied to the embedding.

Every layer l is ``h = x + Mixer_l(RMSNorm(x)); y = h + MLP(RMSNorm(h))``.
Jamba's Mamba has three RMSNorms INSIDE the mixer, on the time-step
input, on B and on C: the mixer is ``ops/selective_scan.py: mamba_mixer``
(which ``models/phi4flash.py`` calls without them). The equations are
written out in ``benchmark/reference_jamba.py``, which the tests hold
this file to.

What the model needs cached (``cache_spec()``): per Mamba layer a
``state`` (the scan's state and the convolution's last inputs, in the
engine's state slots), per attention layer a ``full`` pool. The one K/V
head is stored FOLDED, ``(blocks, block_size, kv_heads * head_dim)``: the
projection's output as it stands, and the only layout the compiled ragged
kernel takes for fewer than two bfloat16 K/V heads; the 20 query heads of
the one K/V head stack on the row axis of one product in the kernel.
Because its recurrent state can be SNAPSHOT at a block boundary, the
engine's prefix cache works for this model
(``serving/block_manager.py``).

Each kind of layer is a ``jax.jit`` of its own, so the 28 layers share
two traces (PERF.md section 6: the set-up trap).
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
from paddle_tpu.models.mla_moe import _raw, _rms_norm
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.ops.selective_scan import mamba_mixer

__all__ = ["JambaConfig", "JambaForCausalLM"]


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_experts: int = 1
    sliding_window: Optional[int] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # None: the ops' own rule (Pallas on a TPU, jnp elsewhere)
    ragged_attn_impl: Optional[str] = None
    scan_impl: Optional[str] = None

    def __post_init__(self):
        if self.num_experts > 1:
            raise ValueError(
                f"num_experts {self.num_experts}: this file builds the "
                f"dense Jamba block (every FFN one SwiGLU); routed experts "
                f"are models/mla_moe.py's")
        if self.sliding_window is not None:
            raise ValueError(
                f"sliding_window {self.sliding_window}: Jamba's attention "
                f"layers are full attention here (the config's null)")
        if not self.tie_word_embeddings or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise ValueError(
                "this Jamba ties the head to the embedding, has a bias on "
                "the convolution and none on the Mamba projections "
                "(tie_word_embeddings, mamba_conv_bias, mamba_proj_bias)")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("query heads must nest in K/V heads and "
                             "divide the hidden size")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    def layer_kind(self, l):
        return ("attention"
                if l % self.attn_layer_period == self.attn_layer_offset
                else "mamba")

    @staticmethod
    def tiny(**kw):
        """The published layout rule at toy widths (tests): 6 layers,
        attention on layer 1 and 4, four query heads on one K/V head."""
        base = dict(vocab_size=160, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=6, num_attention_heads=4,
                    num_key_value_heads=1, attn_layer_period=3,
                    attn_layer_offset=1, max_position_embeddings=256,
                    mamba_d_state=4, mamba_dt_rank=8)
        base.update(kw)
        return JambaConfig(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _mlp_residual(p, h, eps):
    with jax.named_scope("mlp"):
        u = _rms_norm(h, p["norm2_w"], eps)
        g, v = jnp.split(u @ p["gate_up"], 2, axis=-1)
        return h + (g * jax.nn.sigmoid(g) * v) @ p["down"]


@functools.partial(jax.jit, static_argnames=("eps", "scan_impl"))
def _mamba_layer(p, x, state, slots, cu, ctx, ns, *, eps, scan_impl):
    """Returns (layer output (T, d), state')."""
    with jax.named_scope("ssm_proj"):
        u = _rms_norm(x, p["norm1_w"], eps)
    mix, _, state = mamba_mixer(p, u, state, slots, cu, ctx, ns,
                                scan_impl=scan_impl, inner_norm_eps=eps)
    with jax.named_scope("ssm_proj"):
        h = x + mix
    return _mlp_residual(p, h, eps), state


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "impl"))
def _attn_layer(p, x, kc, vc, bt, cu, ctx, ns, *, heads, kv_heads, eps,
                impl):
    """Multi-query attention, no positional encoding: writes its K/V."""
    t, hidden = x.shape
    d = hidden // heads
    with jax.named_scope("attn_proj"):
        u = _rms_norm(x, p["norm1_w"], eps)
        q = (u @ p["q_proj"]).reshape(t, heads, d)
        k = (u @ p["k_proj"]).reshape(t, kv_heads, d)
        v = (u @ p["v_proj"]).reshape(t, kv_heads, d)
    out, kc, vc = ragged_paged_attention(
        q, k, v, kc, vc, bt, cu, ctx, ns, scale=1.0 / math.sqrt(d),
        impl=impl)
    with jax.named_scope("attn_proj"):
        h = x + out.reshape(t, hidden) @ p["o_proj"]
    return _mlp_residual(p, h, eps), kc, vc


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, embed, norm_w, *, eps):
    with jax.named_scope("lm_head"):
        return jnp.dot(_rms_norm(x, norm_w, eps), embed.T,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class JambaLayer(nn.Layer):
    """One layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: JambaConfig, l: int):
        super().__init__()
        c = config
        self.kind = c.layer_kind(l)
        h, e, d = c.hidden_size, c.d_inner, c.head_dim
        ones, zeros = init.Constant(1.0), init.Constant(0.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        mat("norm1_w", [h], default_initializer=ones)
        mat("norm2_w", [h], default_initializer=ones)
        mat("gate_up", [h, 2 * c.intermediate_size])
        mat("down", [c.intermediate_size, h])
        if self.kind == "mamba":
            n, rank = c.mamba_d_state, c.mamba_dt_rank
            mat("in_proj", [h, 2 * e])
            mat("conv_w", [c.mamba_d_conv, e], default_initializer=(
                init.Uniform(-0.5, 0.5)))
            mat("conv_b", [e], default_initializer=zeros)
            mat("x_proj", [e, rank + 2 * n])
            mat("dt_norm", [rank], default_initializer=ones)
            mat("b_norm", [n], default_initializer=ones)
            mat("c_norm", [n], default_initializer=ones)
            mat("dt_w", [rank, e])
            # softplus(dt_b) ~ 0.01 .. 0.1 as Mamba draws its time steps
            mat("dt_b", [e], dtype="float32",
                default_initializer=init.Uniform(-4.6, -2.25))
            mat("A_log", [e, n], dtype="float32",
                default_initializer=init.Assign(np.log(np.tile(
                    np.arange(1, n + 1, dtype=np.float32), (e, 1)))))
            mat("D", [e], dtype="float32", default_initializer=ones)
            mat("out_proj", [e, h])
        else:
            kv = c.num_key_value_heads * d
            mat("q_proj", [h, h])
            mat("k_proj", [h, kv])
            mat("v_proj", [h, kv])
            mat("o_proj", [h, h])

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


class JambaForCausalLM(nn.Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [JambaLayer(config, l)
             for l in range(config.num_hidden_layers)])
        self.final_norm_w = self.create_parameter(
            [config.hidden_size], default_initializer=init.Constant(1.0))

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """Per layer, what is cached (the contract is
        ``models/phi4flash.py: cache_spec``'s): a dict of ``(slots + 1,
        *shape)`` state arrays for a Mamba layer, a ``(K, V)`` pair of
        ``(blocks, block_size, kv_heads * head_dim)`` pools for an
        attention layer."""
        c = self.config
        state = {"kind": "state", "shapes": {
            "ssm": ((c.mamba_d_state, c.d_inner), "float32"),
            "conv": ((c.mamba_d_conv - 1, c.d_inner), None)}}
        return {"kv_shape": (c.num_key_value_heads * c.head_dim,),
                "layers": [dict(state) if c.layer_kind(l) == "mamba"
                           else {"kind": "full"}
                           for l in range(c.num_hidden_layers)]}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, slots, bt, cu, ctx, ns):
        c = self.config
        eps = c.rms_norm_eps
        with jax.named_scope("embed"):
            x = self.embed_tokens.weight._data[ids]
        cache = list(cache)
        for l, layer in enumerate(self.layers):
            p = layer.weights()
            if layer.kind == "mamba":
                x, cache[l] = _mamba_layer(p, x, cache[l], slots, cu, ctx,
                                           ns, eps=eps,
                                           scan_impl=c.scan_impl)
            else:
                x, kc, vc = _attn_layer(
                    p, x, *cache[l], bt, cu, ctx, ns,
                    heads=c.num_attention_heads,
                    kv_heads=c.num_key_value_heads, eps=eps,
                    impl=c.ragged_attn_impl)
                cache[l] = (kc, vc)
        return x, cache

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
        return _head(x[last], self.embed_tokens.weight._data,
                     self.final_norm_w._data,
                     eps=self.config.rms_norm_eps), cache

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
        logits = _head(x, self.embed_tokens.weight._data,
                       self.final_norm_w._data,
                       eps=self.config.rms_norm_eps)
        return Tensor._from_data(logits.reshape(b, t, -1))
