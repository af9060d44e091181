"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder with
differential attention (arXiv:2507.06607; Samba 2406.07522, Mamba
2312.00752, YOCO 2405.05254, Differential Transformer 2410.05258).

Every layer l is ``h = x + Mixer_l(LN(x)); y = h + MLP(LN(h))``; the mixer
by index (``Phi4FlashConfig.layer_kind``): Mamba-1 on the even layers up
to L/2 (the last one also emits its memory, the scan's output before the
gate), window differential attention on the odd layers below L/2, full
differential attention at L/2 + 1 (the model's only full K/V), then gated
memory units (even) and differential cross-attention onto layer L/2 + 1's
pages (odd). No positional encoding. The equations are written out in
``benchmark/reference_phi4flash.py``, which the tests hold this file to.
The Mamba-1 mixer itself (projections, convolution, scan, gate) is
``ops/selective_scan.py: mamba_mixer``, which owns it since
``models/jamba.py`` runs the same mixer with norms inside; this file keeps
the layer around it (its LayerNorm, residual, MLP and the memory output).

What the model needs cached is not one stack of K/V: ``cache_spec()``
says, per layer, ``full``, ``window`` (only the last ``sliding_window``
positions live), ``state`` (per-sequence recurrent state in slots),
``reads`` (another layer's pages, nothing written) or ``none``; the
serving engine builds exactly that and threads it through the step as one
pytree (a list with one entry per layer).

Differential attention through the ragged kernel as it compiles: a K/V
head pair (2g, 2g+1) is stored as ONE cache head of ``2 * head_dim``
lanes, ``[k1 | k2]`` and ``[v1 | v2]``, and a token's pairs side by side
on the lane axis (the kernel's folded layout: ``(blocks, block_size,
kv_heads * head_dim)``, which is the K and V projections' output as it
stands), and the kernel is handed ``[q1 | 0]`` and ``[0 | q2]`` as two
query heads; it returns ``softmax(q1 k1')[v1|v2]`` and
``softmax(q2 k2')[v1|v2]``, and the subtraction, norm and scale are
element-wise after it. Query pair j = heads (2j, 2j+1) reads K/V pair
``j // rep``.

The step does not run every layer on every token (YOCO's prefill): the
self-decoder (layers 0..L/2+1) runs on the (T,) stream, the cross-decoder
on the S rows that can yield a token. Each kind of layer is a ``jax.jit``
of its own, so the 32 layers share five traces (PERF.md section 6: the
set-up trap), and called outside a jit they run kind by kind.
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
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.ops.selective_scan import mamba_mixer

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM"]


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # Mamba-1's defaults: the public config does not carry them
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None      # ceil(hidden / 16)
    # None: the ops' own rule (Pallas on a TPU, jnp elsewhere)
    ragged_attn_impl: Optional[str] = None
    scan_impl: Optional[str] = None
    # "last": the cross-decoder runs on each slot's last row (the
    # serving step); "all": on every row (tests, whole-sequence forward)
    cross_decoder_rows: str = "last"

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_hidden_layers % 2 \
                or self.num_hidden_layers < 4:
            raise ValueError("the SambaY layout rule here is mb_per_layer "
                             "2 over an even depth >= 4")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or (self.num_attention_heads
                    % self.num_key_value_heads):
            raise ValueError("differential attention pairs heads: query "
                             "and K/V head counts must be even and nest")
        if not self.tie_word_embeddings:
            raise ValueError("phi4flash ties the head to the embedding")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def split(self):
        """The first layer of the cross-decoder."""
        return self.num_hidden_layers // 2 + 2

    def layer_kind(self, l):
        half = self.num_hidden_layers // 2
        if l <= half:
            return "mamba" if l % 2 == 0 else "window"
        if l == half + 1:
            return "full"
        return "gmu" if l % 2 == 0 else "cross"

    def lambda_init(self, l):
        return 0.8 - 0.6 * math.exp(-0.3 * l)

    @staticmethod
    def tiny(**kw):
        """The published layout rule and ratios at toy widths (tests)."""
        base = dict(vocab_size=160, hidden_size=64, intermediate_size=256,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, sliding_window=8,
                    max_position_embeddings=256, mamba_d_state=4)
        base.update(kw)
        return Phi4FlashConfig(**base)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays (weights as dicts, [in, out] matrices)
# ---------------------------------------------------------------------------
def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp_residual(p, h, eps):
    with jax.named_scope("mlp"):
        u = _layer_norm(h, p["norm2_w"], p["norm2_b"], eps)
        g, v = jnp.split(u @ p["gate_up"], 2, axis=-1)
        return h + (_silu(g) * v) @ p["down"]


@functools.partial(jax.jit, static_argnames=("eps", "scan_impl"))
def _mamba_layer(p, x, state, slots, cu, ctx, ns, *, eps, scan_impl):
    """Returns (layer output (T, d), state', memory (T, E)). The mixer is
    ``ops/selective_scan.py: mamba_mixer``, which ``models/jamba.py``
    calls too."""
    with jax.named_scope("ssm_proj"):
        u = _layer_norm(x, p["norm1_w"], p["norm1_b"], eps)
    mix, y, state = mamba_mixer(p, u, state, slots, cu, ctx, ns,
                                scan_impl=scan_impl)
    with jax.named_scope("ssm_proj"):
        h = x + mix
    out = _mlp_residual(p, h, eps)
    with jax.named_scope("ssm_proj"):
        memory = y.astype(x.dtype)
    return out, state, memory


def _diff_lambda(p, lam_init):
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                            * p["lambda_k1"].astype(f32)))
            - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                              * p["lambda_k2"].astype(f32)))
            + lam_init)


def _padded_queries(q):
    """(T, H, D) -> (T, H, 2D): even heads ``[q | 0]``, odd ``[0 | q]``."""
    z = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[1]) % 2 == 1)[None, :, None]
    return jnp.where(odd, jnp.concatenate([z, q], axis=-1),
                     jnp.concatenate([q, z], axis=-1))


def _diff_combine(out, lam, lam_init, eps):
    """The kernel's (T, H, 2D) -> (T, H/2 * 2D): A1 - lam A2 per query
    pair, RMSNorm over 2D, times (1 - lam_init)."""
    o = out.astype(jnp.float32)
    o = o[:, 0::2] - lam * o[:, 1::2]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return ((1.0 - lam_init) * o).reshape(o.shape[0], -1).astype(out.dtype)


def _diff_attend(p, u, kc, vc, bt, cu, ctx, ns, lam_init, *, heads,
                 kv_heads, window, eps, impl, write):
    t, hidden = u.shape
    d = hidden // heads
    with jax.named_scope("attn_proj"):
        q = _padded_queries((u @ p["q_proj"]).reshape(t, heads, d))
        k = v = None
        if write:
            # adjacent K/V heads are adjacent in the projection's output:
            # a pair [k1 | k2] is one reshape away
            k = (u @ p["k_proj"]).reshape(t, kv_heads // 2, 2 * d)
            v = (u @ p["v_proj"]).reshape(t, kv_heads // 2, 2 * d)
    out, kc, vc = ragged_paged_attention(
        q, k, v, kc, vc, bt, cu, ctx, ns, scale=1.0 / math.sqrt(d),
        impl=impl, window=window)
    o = _diff_combine(out, _diff_lambda(p, lam_init), lam_init, eps)
    with jax.named_scope("attn_proj"):
        return o @ p["o_proj"], kc, vc


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window",
                                             "eps", "impl"))
def _attn_layer(p, x, kc, vc, bt, cu, ctx, ns, lam_init, *, heads,
                kv_heads, window, eps, impl):
    """Differential self-attention, window or full: writes its K/V."""
    with jax.named_scope("window_attention" if window else
                         "full_attention"):
        u = _layer_norm(x, p["norm1_w"], p["norm1_b"], eps)
        mix, kc, vc = _diff_attend(
            p, u, kc, vc, bt, cu, ctx, ns, lam_init, heads=heads,
            kv_heads=kv_heads, window=window, eps=eps, impl=impl,
            write=True)
        h = x + mix
    return _mlp_residual(p, h, eps), kc, vc


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "impl"))
def _cross_layer(p, x, kc, vc, bt, cu, ctx, ns, lam_init, *, heads,
                 kv_heads, eps, impl):
    """Differential cross-attention onto another layer's pages (full,
    causal): own W_q, W_o and lambdas, nothing written."""
    with jax.named_scope("cross_attention"):
        u = _layer_norm(x, p["norm1_w"], p["norm1_b"], eps)
        mix, _, _ = _diff_attend(
            p, u, kc, vc, bt, cu, ctx, ns, lam_init, heads=heads,
            kv_heads=kv_heads, window=None, eps=eps, impl=impl,
            write=False)
        h = x + mix
    return _mlp_residual(p, h, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _gmu_layer(p, x, memory, *, eps):
    with jax.named_scope("gmu"):
        u = _layer_norm(x, p["norm1_w"], p["norm1_b"], eps)
        mix = (memory * _silu(u @ p["in_proj"])) @ p["out_proj"]
        h = x + mix
    return _mlp_residual(p, h, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, embed, norm_w, norm_b, *, eps):
    with jax.named_scope("lm_head"):
        return jnp.dot(_layer_norm(x, norm_w, norm_b, eps), embed.T,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
class Phi4FlashLayer(nn.Layer):
    """One layer's parameters under the reference's names; the
    mathematics is in the functions above."""

    def __init__(self, config: Phi4FlashConfig, l: int):
        super().__init__()
        c = config
        self.kind = c.layer_kind(l)
        h, e, d = c.hidden_size, c.d_inner, c.head_dim
        ones, zeros = init.Constant(1.0), init.Constant(0.0)

        def mat(name, shape, **kw):
            setattr(self, name, self.create_parameter(list(shape), **kw))

        mat("norm1_w", [h], default_initializer=ones)
        mat("norm1_b", [h], default_initializer=zeros)
        mat("norm2_w", [h], default_initializer=ones)
        mat("norm2_b", [h], default_initializer=zeros)
        mat("gate_up", [h, 2 * c.intermediate_size])
        mat("down", [c.intermediate_size, h])
        if self.kind == "mamba":
            n, rank = c.mamba_d_state, c.mamba_dt_rank
            mat("in_proj", [h, 2 * e])
            mat("conv_w", [c.mamba_d_conv, e], default_initializer=(
                init.Uniform(-0.5, 0.5)))
            mat("conv_b", [e], default_initializer=zeros)
            mat("x_proj", [e, rank + 2 * n])
            mat("dt_w", [rank, e])
            # softplus(dt_b) ~ 0.01 .. 0.1 as Mamba draws its time steps
            mat("dt_b", [e], dtype="float32",
                default_initializer=init.Uniform(-4.6, -2.25))
            mat("A_log", [e, n], dtype="float32",
                default_initializer=init.Assign(np.log(np.tile(
                    np.arange(1, n + 1, dtype=np.float32), (e, 1)))))
            mat("D", [e], dtype="float32", default_initializer=ones)
            mat("out_proj", [e, h])
        elif self.kind == "gmu":
            mat("in_proj", [h, e])
            mat("out_proj", [e, h])
        else:
            mat("q_proj", [h, h])
            if self.kind != "cross":
                mat("k_proj", [h, c.num_key_value_heads * d])
                mat("v_proj", [h, c.num_key_value_heads * d])
            mat("o_proj", [h, h])
            for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                         "lambda_k2"):
                mat(name, [d], default_initializer=init.Normal(0.0, 0.1))

    def weights(self):
        return {name: p._data for name, p in self._parameters.items()}


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class Phi4FlashForCausalLM(nn.Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Phi4FlashLayer(config, l)
             for l in range(config.num_hidden_layers)])
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_eps)

    # -- what the serving engine has to hold ----------------------------
    def cache_spec(self):
        """Per layer, what is cached; the engine builds it and hands it
        to ``forward_ragged`` as a list with one entry per layer: a
        ``(K, V)`` pair of ``(blocks, block_size, *kv_shape)`` pools for
        ``full`` and ``window`` layers, a dict of ``(slots + 1, *shape)``
        arrays for ``state`` layers (the last slot is scratch), None
        otherwise. ``kv_shape`` is one token's K (or V) entry: here all
        K/V heads side by side, the projection's output as it stands."""
        c = self.config
        full = c.num_hidden_layers // 2 + 1
        layers = []
        for l in range(c.num_hidden_layers):
            kind = c.layer_kind(l)
            if kind == "mamba":
                layers.append({"kind": "state", "shapes": {
                    "ssm": ((c.mamba_d_state, c.d_inner), "float32"),
                    "conv": ((c.mamba_d_conv - 1, c.d_inner), None)}})
            elif kind == "window":
                layers.append({"kind": "window",
                               "window": c.sliding_window})
            elif kind == "full":
                layers.append({"kind": "full"})
            elif kind == "cross":
                layers.append({"kind": "reads", "layer": full})
            else:
                layers.append({"kind": "none"})
        # 10 pairs [k1 | k2] (or [v1 | v2]) of 128 lanes at the
        # published widths
        return {"kv_shape": (c.num_key_value_heads * c.head_dim,),
                "layers": layers}

    # -- the step --------------------------------------------------------
    def _run(self, ids, cache, tables, bt, cu, ctx, ns, rows):
        c = self.config
        eps = c.layer_norm_eps
        attn = dict(heads=c.num_attention_heads,
                    kv_heads=c.num_key_value_heads, eps=eps,
                    impl=c.ragged_attn_impl)
        with jax.named_scope("embed"):
            x = self.embed_tokens.weight._data[ids]
        cache = list(cache)
        memory = None
        full = c.split - 1
        for l in range(c.split):
            p = self.layers[l].weights()
            kind = c.layer_kind(l)
            lam = jnp.float32(c.lambda_init(l))
            if kind == "mamba":
                x, cache[l], mem = _mamba_layer(
                    p, x, cache[l], tables["slots"], cu, ctx, ns, eps=eps,
                    scan_impl=c.scan_impl)
                if l == c.num_hidden_layers // 2:
                    memory = mem
            elif kind == "window":
                x, kc, vc = _attn_layer(
                    p, x, *cache[l], tables["window"], cu, ctx, ns, lam,
                    window=c.sliding_window, **attn)
                cache[l] = (kc, vc)
            else:
                x, kc, vc = _attn_layer(p, x, *cache[l], bt, cu, ctx, ns,
                                        lam, window=None, **attn)
                cache[l] = (kc, vc)
        # the cross-decoder: on each slot's last row (a mid-prompt
        # chunk's row is computed and never sampled), or on every row
        if rows == "last":
            s_slots = ctx.shape[0]
            last = jnp.clip(cu[1:] - 1, 0, x.shape[0] - 1)
            x, memory = x[last], memory[last]
            cu = jnp.minimum(jnp.arange(s_slots + 1, dtype=jnp.int32), ns)
        for l in range(c.split, c.num_hidden_layers):
            p = self.layers[l].weights()
            if c.layer_kind(l) == "gmu":
                x = _gmu_layer(p, x, memory, eps=eps)
            else:
                x = _cross_layer(p, x, *cache[full], bt, cu, ctx, ns,
                                 jnp.float32(c.lambda_init(l)), **attn)
        logits = _head(x, self.embed_tokens.weight._data,
                       self.final_norm.weight._data,
                       self.final_norm.bias._data, eps=eps)
        return logits, cache

    def forward_ragged(self, input_ids, cache, tables, block_tables,
                       cu_seqlens, context_lens, num_seqs):
        """The engine's step. ``input_ids`` (T,) ragged-packed;
        ``cache`` as ``cache_spec`` describes; ``tables``: ``window``
        (S, MB) block table of the window pools (entries behind the
        window may be -1) and ``slots`` (S,) state slots;
        ``block_tables`` (S, MB) of the full pool. Returns (logits
        (S, vocab) float32 at each slot's last row, cache')."""
        cu = _raw(cu_seqlens).astype(jnp.int32)
        logits, cache = self._run(
            _raw(input_ids).reshape(-1), cache,
            {k: _raw(v).astype(jnp.int32) for k, v in tables.items()},
            _raw(block_tables).astype(jnp.int32), cu,
            _raw(context_lens).astype(jnp.int32),
            _raw(num_seqs).astype(jnp.int32),
            self.config.cross_decoder_rows)
        if self.config.cross_decoder_rows == "all":
            logits = logits[jnp.clip(cu[1:] - 1, 0, logits.shape[0] - 1)]
        return logits, cache

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
            elif lay["kind"] in ("full", "window"):
                cache.append((jnp.zeros(pool, dtype),
                              jnp.zeros(pool, dtype)))
            else:
                cache.append(None)
        table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        logits, _ = self._run(
            jnp.asarray(ids.reshape(-1), jnp.int32), cache,
            {"window": table, "slots": jnp.arange(b, dtype=jnp.int32)},
            table, jnp.arange(b + 1, dtype=jnp.int32) * t,
            jnp.full((b,), t, jnp.int32), jnp.int32(b), "all")
        return Tensor._from_data(logits.reshape(b, t, -1))
