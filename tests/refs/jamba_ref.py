"""Plain reference of the Jamba block (arXiv:2403.19887, the ``jamba``
model type) as AI21-Jamba2-3B configures it: the forward pass of ONE
sequence from position 0 in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching, no state slots; every function takes its weights as a plain
dict, and the model is run layer by layer (``run_layer``, which depends on
a layer's index only through its kind), so a caller may hold one layer's
float32 weights at a time and compile one program a kind.

Weights are ``[in, out]`` matrices (``y = x @ W``). Every layer l is
``h = x + Mixer_l(RMSNorm(x)); y = h + MLP(RMSNorm(h))`` with
``MLP(u) = W_down(SiLU(W_gate u) * W_up u)``; the mixer is causal
multi-query attention WITHOUT any positional encoding where
``l % attn_layer_period == attn_layer_offset`` and Mamba-1 everywhere
else. Jamba's Mamba differs from the plain one (``reference_phi4flash``)
by three RMSNorms inside the mixer: on the time-step input, on B and on
C, each with a learned weight. The head is tied to the embedding.

Departures from the published model, none of which the mathematics sees:
``gate_up`` holds ``[W_gate | W_up]`` side by side (a storage layout), and
the weights a caller passes are the program's own initialisers from a
seed, not the checkpoint's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
# keys of the public config this file reads
CFG_KEYS = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "hidden_size", "attn_layer_period",
            "attn_layer_offset", "rms_norm_eps")


def layer_kind(l, cfg):
    return ("attention"
            if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba")


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(u, p):
    """W_down(SiLU(W_gate u) * W_up u), ``gate_up`` = [W_gate | W_up]."""
    g, v = jnp.split(u @ p["gate_up"], 2, axis=-1)
    return (silu(g) * v) @ p["down"]


def mamba(u, p, eps):
    """Jamba's Mamba-1 over one whole sequence u (T, d) from zero state:
    (T, d)."""
    x, z = jnp.split(u @ p["in_proj"], 2, axis=-1)       # (T, E) each
    taps = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    xc = sum(xp[j:j + x.shape[0]] * p["conv_w"][j] for j in range(taps))
    xc = silu(xc + p["conv_b"])
    n = p["A_log"].shape[1]
    rank = p["dt_w"].shape[0]
    rbc = xc @ p["x_proj"]
    r = rms_norm(rbc[:, :rank], p["dt_norm"], eps)
    bm = rms_norm(rbc[:, rank:rank + n], p["b_norm"], eps)
    cm = rms_norm(rbc[:, rank + n:], p["c_norm"], eps)
    dt = jax.nn.softplus(r @ p["dt_w"] + p["dt_b"])      # (T, E)
    a = -jnp.exp(p["A_log"])                             # (E, N)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (dt, xc, bm, cm))
    y = y + p["D"] * xc
    return (y * silu(z)) @ p["out_proj"]


def attention(u, p, cfg, block=256):
    """Causal attention of one sequence, no positional encoding: query
    head h reads K/V head ``h // rep``. Dense scores, computed a block of
    queries at a time so that a long history fits."""
    t = u.shape[0]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    q = (u @ p["q_proj"]).reshape(t, h, d)
    k = jnp.repeat((u @ p["k_proj"]).reshape(t, kh, d), h // kh, axis=1)
    v = jnp.repeat((u @ p["v_proj"]).reshape(t, kh, d), h // kh, axis=1)
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
        u = rms_norm(x, p["norm1_w"], eps)
        mix = (mamba(u, p, eps) if kind == "mamba"
               else attention(u, p, cfg))
        h = x + mix
        return h + mlp(rms_norm(h, p["norm2_w"], eps), p)


def head(x, embed, norm_w, cfg):
    """Final RMSNorm and the tied head: logits (rows, vocab)."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ embed.T


def forward(params, tokens, cfg):
    """Logits (T, vocab) of one sequence. ``params``: ``embed`` (V, d),
    ``norm_w``, ``layers`` (a list of per-layer dicts)."""
    x = params["embed"][jnp.asarray(tokens)]
    for l, p in enumerate(params["layers"]):
        x = run_layer(layer_kind(l, cfg), p, x, cfg)
    return head(x, params["embed"], params["norm_w"], cfg)
