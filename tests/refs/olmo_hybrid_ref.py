"""Plain reference of the Olmo Hybrid block (the ``olmo_hybrid`` model
type) as Olmo-Hybrid-7B configures it: the forward pass of ONE sequence
from position 0 in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching, no state slots, no chunking; every function takes its weights
as a plain dict, and the model is run layer by layer (``run_layer``,
which depends on a layer's index only through its kind), so a caller may
hold one layer's float32 weights at a time and compile one program a
kind.

Weights are ``[in, out]`` matrices (``y = x @ W``). Every layer is
Olmo's post-norm block, ``h = x + RMSNorm_a(Mixer(x)); y = h +
RMSNorm_f(MLP(h))`` with ``MLP(u) = W_down(SiLU(W_gate u) * W_up u)``.
A ``full_attention`` layer is causal multi-head attention with q and k
RMS-normed over their whole width (QK-norm) and no positional encoding.
A ``linear_attention`` layer is Gated DeltaNet (arXiv:2412.06464): q, k,
v through a causal depthwise convolution and SiLU, q and k L2-normalised
per head (q then scaled by dk^-1/2), and per head, token by token, with
the state ``S`` (dv, dk) from zero:

    beta_t = 2 sigmoid(x_t W_b)            (negative eigenvalues allowed)
    g_t    = -exp(A_log) softplus(x_t W_a + dt_bias)
    S_t    = e^{g_t} S_{t-1} + beta_t (v_t - e^{g_t} S_{t-1} k_t) k_t^T
    o_t    = S_t q_t

and the output ``W_o [RMSNorm_dv(o_t) w * SiLU(x_t W_g)]``, the norm per
head. The head is untied.

Departures from the published model, none of which the mathematics sees:
``gate_up`` holds ``[W_gate | W_up]``, ``qkv_proj`` ``[W_q | W_k | W_v]``
and ``ab_proj`` ``[W_a | W_b]`` side by side, and the three convolutions
are one of ``2 dk H + dv H`` channels (storage layouts); the weights a
caller passes are the program's own initialisers from a seed, not the
checkpoint's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
# keys of the public config this file reads
CFG_KEYS = ("num_hidden_layers", "num_attention_heads", "hidden_size",
            "rms_norm_eps", "layer_types", "linear_num_key_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_allow_neg_eigval")
L2_EPS = 1e-6


def layer_kind(l, cfg):
    return cfg["layer_types"][l]


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(u, p):
    """W_down(SiLU(W_gate u) * W_up u), ``gate_up`` = [W_gate | W_up]."""
    g, v = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (silu(g) * v) @ p["down"]


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token from zero state, one head at a time
    in the state's own orientation (dv, dk): q, k (T, H, dk), v (T, H,
    dv), g, beta (T, H). Returns o (T, H, dv)."""
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp                  # (H, .)
        a = jnp.exp(g_t)[:, None, None]
        pred = jnp.einsum("hvk,hk->hv", a * s, k_t)
        s = a * s + jnp.einsum("hv,hk->hvk",
                               b_t[:, None] * (v_t - pred), k_t)
        return s, jnp.einsum("hvk,hk->hv", s, q_t)

    h, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((h, dv, dk), jnp.float32),
                        (q, k, v, g, beta))
    return o


def gated_deltanet(x, p, cfg):
    """The linear-attention mixer over one whole sequence x (T, d)."""
    t = x.shape[0]
    h = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkv = x @ p["qkv_proj"]
    taps = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    qkv = silu(sum(xp[j:j + t] * p["conv_w"][j] for j in range(taps)))

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    q = l2(qkv[:, :h * dk].reshape(t, h, dk)) / math.sqrt(dk)
    k = l2(qkv[:, h * dk:2 * h * dk].reshape(t, h, dk))
    v = qkv[:, 2 * h * dk:].reshape(t, h, dv)
    a_in, b_in = jnp.split(x @ p["ab_proj"], 2, axis=-1)
    beta = jax.nn.sigmoid(b_in) * (2.0 if cfg["linear_allow_neg_eigval"]
                                   else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_in + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm_w"], cfg["rms_norm_eps"]).reshape(t, h * dv)
    return (o * silu(x @ p["g_proj"])) @ p["o_proj"]


def attention(x, p, cfg, block=256):
    """Causal multi-head attention of one sequence with QK-norm and no
    positional encoding. Dense scores, computed a block of queries at a
    time so that a long history fits."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ p["q_proj"], p["q_norm_w"], eps).reshape(t, h, d)
    k = rms_norm(x @ p["k_proj"], p["k_norm_w"], eps).reshape(t, h, d)
    v = (x @ p["v_proj"]).reshape(t, h, d)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, d)
    pos = jnp.arange(t)

    def one(args):
        i, qi = args                                     # qi (block, H, D)
        qpos = i * block + jnp.arange(block)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(d)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (jnp.arange(qb.shape[0]), qb))
    return o.reshape(-1, h * d)[:t] @ p["o_proj"]


def run_layer(kind, p, x, cfg):
    """One layer of ``kind`` over the whole sequence x (T, d)."""
    with jax.default_matmul_precision(HIGHEST):
        eps = cfg["rms_norm_eps"]
        mix = (gated_deltanet(x, p, cfg) if kind == "linear_attention"
               else attention(x, p, cfg))
        h = x + rms_norm(mix, p["attn_norm_w"], eps)
        return h + rms_norm(mlp(h, p), p["ff_norm_w"], eps)


def head(x, lm_head, norm_w, cfg):
    """Final RMSNorm and the untied head: logits (rows, vocab)."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def forward(params, tokens, cfg):
    """Logits (T, vocab) of one sequence. ``params``: ``embed`` (V, d),
    ``norm_w``, ``lm_head`` (d, V), ``layers`` (a list of per-layer
    dicts)."""
    x = params["embed"][jnp.asarray(tokens)]
    for l, p in enumerate(params["layers"]):
        x = run_layer(layer_kind(l, cfg), p, x, cfg)
    return head(x, params["lm_head"], params["norm_w"], cfg)
