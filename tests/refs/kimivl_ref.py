"""Plain reference of the Kimi-VL-A3B decoder (a DeepSeek-V3-style block:
multi-head latent attention, sigmoid-routed experts beside shared ones):
the forward pass of ONE sequence in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``. No cache, no
kernels, no batching. Attention is in the EXPANDED form (per-head keys
and values from ``W_kvb``), so it shares no formula with the program's
absorbed path; the experts are a loop over all of them with a mask.
Weights are plain dicts of ``[in, out]`` matrices (``y = x @ W``) under
the program's names, and the model is run layer by layer (``run_layer``)
so that a caller may hold one layer's float32 weights at a time.

Layer l: ``h = x + Attn(RMSNorm(x)); y = h + FFN_l(RMSNorm(h))``.

Attention, 16 heads, per head a 128-wide part without position and a
64-wide part with it, values 128 wide; u the normed input at position p:
``q = W_q u`` split per head into ``q_n | q_r``; ``[c_raw | k_raw] =
W_kva u``; ``c = RMSNorm_512(c_raw)``; ``k_r = RoPE(k_raw, p)`` shared by
all heads; ``[k_n,h | v_h] = W_kvb,h c``; ``score_h(p, s) = (q_n,h .
k_n,h(s) + RoPE(q_r,h, p) . k_r(s)) / sqrt(192)``, causal softmax,
``out = W_o [o_1 .. o_16]``. RoPE pairs dim i with dim i + 32 of the
64-wide slice (the half-split layout, a storage convention).

FFN, layer 0: ``W_down(SiLU(W_gate u) * W_up u)``. Layers 1..: scores
``s = sigmoid(W_g u)``; the 6 largest of ``s + b`` are the set K; weights
``w_e = s_e / (sum_K s + 1e-20) * 2.446`` (the scores WITHOUT b);
``FFN(u) = sum_K w_e E_e(u) + Shared(u)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "first_k_dense_replace")


def layer_kind(l, cfg):
    return "dense" if l < cfg["first_k_dense_replace"] else "moe"


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, pos, theta):
    """``x`` (T, ..., D) rotated at positions ``pos`` (T,): dims (i,
    i + D/2) are a pair turned by ``pos * theta ** (-2 i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, D/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, cfg):
    """Expanded multi-head latent attention over one whole sequence."""
    t = u.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    q = (u @ p["q_proj"]).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])
    ckr = u @ p["kv_a"]
    c = rms_norm(ckr[:, :rank], p["kv_norm_w"], cfg["rms_norm_eps"])
    k_r = rope(ckr[:, rank:], pos, cfg["rope_theta"])       # (T, dr)
    kv = (c @ p["kv_b"]).reshape(t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    causal = pos[:, None] >= pos[None, :]

    def head(args):                       # one head at a time: (T, T)
        qn, qr, kn, vh = args
        s = (qn @ kn.T + qr @ k_r.T) / math.sqrt(dn + dr)
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0)
                                for a in (q_n, q_r, k_n, v)))
    return jnp.moveaxis(o, 0, 1).reshape(t, h * dv) @ p["o_proj"]


def swiglu(u, gate_up, down):
    g, v = jnp.split(u @ gate_up, 2, axis=-1)
    return (silu(g) * v) @ down


def route(u, p, cfg, routing=None):
    """(sets used (T, K), weights (T, K), the reference's own sets
    (T, K), selection scores s + b (T, E)). ``routing`` = (sets (T, K),
    forced (T,) bool): where forced, the given set is used in place of
    the reference's own choice (its weights are still the reference's
    scores)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ p["router"])
    sel = s + p["router_bias"]
    _, own = jax.lax.top_k(sel, k)
    sets = own
    if routing is not None:
        given, forced = routing
        sets = jnp.where(forced[:, None], given, own)
    w = jnp.take_along_axis(s, sets, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sets, w * cfg["routed_scaling_factor"], own, sel


def experts(u, p, sets, w):
    """Every expert on every token, masked: no sort, no grouping."""
    e = p["experts_gate_up"].shape[0]
    per_expert = jnp.sum(
        jnp.where(sets[:, :, None] == jnp.arange(e)[None, None, :],
                  w[:, :, None], 0.0), axis=1)              # (T, E)

    def one(acc, x):
        gate_up, down, we = x
        return acc + we[:, None] * swiglu(u, gate_up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate_up"], p["experts_down"], per_expert.T))
    return out


def run_layer(kind, p, x, cfg, routing=None):
    """One layer over one whole sequence x (T, d). Returns (y, info):
    info is {} for a dense layer; for an expert layer {"sets": the sets
    used, "own": the reference's own choice on this layer's input
    (the same unless ``routing`` forced another), "sel": s + b}."""
    with jax.default_matmul_precision(HIGHEST):
        eps = cfg["rms_norm_eps"]
        h = x + attention(rms_norm(x, p["norm1_w"], eps), p, cfg)
        u = rms_norm(h, p["norm2_w"], eps)
        if kind == "dense":
            return h + swiglu(u, p["gate_up"], p["down"]), {}
        sets, w, own, sel = route(u, p, cfg, routing)
        y = experts(u, p, sets, w) + swiglu(u, p["shared_gate_up"],
                                            p["shared_down"])
        return h + y, {"sets": sets, "own": own, "sel": sel}


def head(x, lm_head, norm_w, cfg):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def forward(weights, ids, cfg, routing=None):
    """The whole model over one sequence ``ids`` (T,). ``weights``:
    {"embed" (V, d), "layers" [dict], "norm_w", "lm_head" (d, V)};
    ``routing``: None or one entry per layer (None for a dense one).
    Returns (logits (T, V), [info per layer])."""
    x = weights["embed"][ids]
    infos = []
    for l, p in enumerate(weights["layers"]):
        x, info = run_layer(layer_kind(l, cfg), p, x, cfg,
                            None if routing is None else routing[l])
        infos.append(info)
    return head(x, weights["lm_head"], weights["norm_w"], cfg), infos


def dispute_margin(sel, own, other):
    """Per token, how far apart the reference's own selection scores
    ``sel`` (T, E) put the experts two sets (T, K) disagree on: the
    largest score among ``own`` not in ``other`` minus the smallest among
    ``other`` not in ``own`` (0 where the sets agree). A near-tie reads
    near 0."""
    e = sel.shape[1]
    in_own = jnp.any(own[:, :, None] == jnp.arange(e), axis=1)
    in_other = jnp.any(other[:, :, None] == jnp.arange(e), axis=1)
    hi = jnp.max(jnp.where(in_own & ~in_other, sel, -jnp.inf), axis=1)
    lo = jnp.min(jnp.where(in_other & ~in_own, sel, jnp.inf), axis=1)
    return jnp.where(jnp.isfinite(hi) & jnp.isfinite(lo), hi - lo, 0.0)
