"""Plain reference of the SambaY decoder-hybrid-decoder with differential
attention (Phi-4-mini-flash-reasoning, arXiv:2507.06607): the forward pass
of ONE sequence in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching; every function takes its weights as a plain dict, and the model
is run layer by layer (``run_layer``, which depends on a layer's index
only through its kind and its ``lambda_init``), so a caller may hold one
layer's float32 weights at a time and compile one program a kind.

Weights are ``[in, out]`` matrices (``y = x @ W``). Every layer l is
``h = x + Mixer_l(LN(x)); y = h + MLP(LN(h))`` and the mixer by index is
``layer_kind(l, L)``: Mamba-1 on the even layers up to L/2 (the last of
them also emits its memory, the scan's output before the gate), window
differential attention on the odd ones below L/2, full differential
attention at L/2 + 1 (the model's only full K/V), then gated memory units
on the even layers and differential cross-attention onto layer L/2 + 1's
keys and values on the odd ones. No positional encoding anywhere.

Head pairing (a storage layout): query heads (2j, 2j+1) are pair j, K/V
heads (2g, 2g+1) are pair g, query pair j reads K/V pair j // rep.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def layer_kind(l, n_layers):
    half = n_layers // 2
    if l <= half:
        return "mamba" if l % 2 == 0 else "window"
    if l == half + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(u, p):
    """W_down(SiLU(g) * v), [g, v] = W_gate_up u."""
    gv = u @ p["gate_up"]
    g, v = jnp.split(gv, 2, axis=-1)
    return (silu(g) * v) @ p["down"]


def mamba(u, p):
    """Mamba-1 over one whole sequence u (T, d) from zero state. Returns
    (mixer output (T, d), y before the gate (T, d_inner))."""
    xz = u @ p["in_proj"]
    x, z = jnp.split(xz, 2, axis=-1)                     # (T, E) each
    taps = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    xc = sum(xp[j:j + x.shape[0]] * p["conv_w"][j] for j in range(taps))
    xc = silu(xc + p["conv_b"])
    n = p["A_log"].shape[1]
    rank = p["dt_w"].shape[0]
    rbc = xc @ p["x_proj"]
    r, bm, cm = rbc[:, :rank], rbc[:, rank:rank + n], rbc[:, rank + n:]
    dt = jax.nn.softplus(r @ p["dt_w"] + p["dt_b"])      # (T, E)
    a = -jnp.exp(p["A_log"])                             # (E, N)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (dt, xc, bm, cm))
    y = y + p["D"] * xc
    return (y * silu(z)) @ p["out_proj"], y


def diff_lambda(p, lam_init):
    return (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
            + lam_init)


def diff_attention(q, k, v, lam, lam_init, window, eps):
    """Causal differential attention of one sequence. q (T, H, D), k and
    v (T, KH, D). P = softmax(q1 k1' / sqrt(D)) - lam softmax(q2 k2' /
    sqrt(D)); o = (1 - lam_init) RMSNorm_2D(P [v1 | v2]). ``window`` w:
    a query at position p sees keys p-w+1..p. Returns (T, H/2 * 2D)."""
    t, h, d = q.shape
    kh = k.shape[1]
    rep = (h // 2) // (kh // 2)
    pos = jnp.arange(t)
    ok = pos[None, :] <= pos[:, None]
    if window is not None:
        ok = ok & (pos[None, :] > pos[:, None] - window)
    bias = jnp.where(ok, 0.0, -jnp.inf)
    outs = []
    for g in range(kh // 2):
        k1, k2 = k[:, 2 * g], k[:, 2 * g + 1]
        vv = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        for j in range(g * rep, (g + 1) * rep):
            q1, q2 = q[:, 2 * j], q[:, 2 * j + 1]
            p1 = jax.nn.softmax(q1 @ k1.T / math.sqrt(d) + bias, axis=-1)
            p2 = jax.nn.softmax(q2 @ k2.T / math.sqrt(d) + bias, axis=-1)
            o = (p1 - lam * p2) @ vv                     # (T, 2D)
            o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            outs.append((1.0 - lam_init) * o)
    return jnp.concatenate(outs, axis=-1)


def attention(u, p, lam_init, cfg, window, kv=None):
    """Differential self-attention (``kv`` None: keys and values from u,
    which are also returned) or cross-attention onto ``kv`` = (k, v)."""
    t = u.shape[0]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    q = (u @ p["q_proj"]).reshape(t, h, d)
    if kv is None:
        kv = ((u @ p["k_proj"]).reshape(t, kh, d),
              (u @ p["v_proj"]).reshape(t, kh, d))
    o = diff_attention(q, kv[0], kv[1], diff_lambda(p, lam_init), lam_init,
                       window, cfg["layer_norm_eps"])
    return o @ p["o_proj"], kv


def gmu(u, m, p):
    """Gated memory unit: W_2(m * SiLU(W_1 u)), m the memory of the last
    Mamba layer at the same position."""
    return (m * silu(u @ p["in_proj"])) @ p["out_proj"]


def run_layer(kind, p, x, carry, cfg, lam_init=0.0, emits_memory=False):
    """One layer of ``kind`` over the whole sequence x (T, d). ``carry``
    holds what later layers read: ``memory`` (the y of the Mamba layer
    that ``emits_memory``, the last one) and ``kv`` (the full-attention
    layer's keys and values). ``lam_init`` is the attention layers'
    ``lambda_init(l)``."""
    with jax.default_matmul_precision(HIGHEST):
        eps = cfg["layer_norm_eps"]
        u = layer_norm(x, p["norm1_w"], p["norm1_b"], eps)
        carry = dict(carry)
        if kind == "mamba":
            mix, y = mamba(u, p)
            if emits_memory:
                carry["memory"] = y
        elif kind == "window":
            mix, _ = attention(u, p, lam_init, cfg, cfg["sliding_window"])
        elif kind == "full":
            mix, carry["kv"] = attention(u, p, lam_init, cfg, None)
        elif kind == "gmu":
            mix = gmu(u, carry["memory"], p)
        else:
            mix, _ = attention(u, p, lam_init, cfg, None, kv=carry["kv"])
        h = x + mix
        return h + mlp(layer_norm(h, p["norm2_w"], p["norm2_b"], eps),
                       p), carry


def head(x, embed, norm_w, norm_b, cfg):
    """Final LayerNorm and the tied head: logits (rows, vocab)."""
    with jax.default_matmul_precision(HIGHEST):
        return layer_norm(x, norm_w, norm_b,
                          cfg["layer_norm_eps"]) @ embed.T


def forward(params, tokens, cfg):
    """Logits (T, vocab) of one sequence. ``params``: ``embed`` (V, d),
    ``norm_w``/``norm_b``, ``layers`` (a list of per-layer dicts)."""
    x = params["embed"][jnp.asarray(tokens)]
    carry = {}
    n = cfg["num_hidden_layers"]
    for l, p in enumerate(params["layers"]):
        x, carry = run_layer(layer_kind(l, n), p, x, carry, cfg,
                             lam_init=lambda_init(l),
                             emits_memory=(l == n // 2))
    return head(x, params["embed"], params["norm_w"], params["norm_b"], cfg)
