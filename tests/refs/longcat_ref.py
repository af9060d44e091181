"""Plain reference of the LongCat-Flash decoder (catalog row
``LongCat-Flash-Chat``, arXiv:2509.01322, ``model_type`` ``longcat_flash``):
the forward pass of ONE sequence in straightforward ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching. Attention is in the EXPANDED form (per-head keys and values from
the latent), the experts are a masked loop over the ones held plus the
identity term. Weights are plain dicts of ``[in, out]`` matrices (``y = x @
W``) under the program's names; the model is run layer by layer
(``run_layer``), attention a head at a time and a block of queries at a
time, the dense FFNs a block of rows at a time and the experts one at a
time (each matrix upcast where it is used), so that a 4k history fits
beside the served weights.

Layer l (a "double layer", x in R^hidden)::

    a1 = x  + MLA_0(RMSNorm_in0(x))
    u  = RMSNorm_post0(a1)
    m  = MoE(u)                          # the shortcut branch
    b1 = a1 + FFN_0(u)
    a2 = b1 + MLA_1(RMSNorm_in1(b1))
    y  = a2 + FFN_1(RMSNorm_post1(a2)) + m

then ``RMSNorm_final`` and the untied head over the vocabulary rows held.
``FFN(v) = W_down(SiLU(W_gate v) * W_up v)``, ``ffn_hidden_size`` wide.

MoE(u), ``n_routed_experts`` routed + ``zero_expert_num`` identity experts
(ids ``n_routed .. n_routed + zero - 1``), no shared expert: ``p =
softmax(W_r u)`` over all columns; the ``moe_topk`` largest ``p + b`` are
the set K; ``w_k = routed_scaling_factor * p_k`` (not normalised);
``MoE(u) = sum_{k in K, k routed and held} w_k E_k(u) + sum_{k in K,
identity} w_k u``. What the routed experts this chip does not hold would
add is left out, as in the program.

MLA(h), H heads: ``c_q = a_q RMSNorm_q(W_qa h)``, ``a_q = sqrt(hidden /
q_lora_rank)``; ``[q_n,i | q_r,i] = W_qb,i c_q``; ``[c_raw | k_raw] =
W_kva h``; ``c = a_kv RMSNorm_kv(c_raw)``, ``a_kv = sqrt(hidden /
kv_lora_rank)``; ``k_r = RoPE(k_raw)`` (one a token, shared by the heads);
``k_n,i = W_UK,i c``, ``v_i = W_UV,i c``; ``o_i(t) = sum_{s <= t}
softmax((q_n,i . k_n,i + RoPE(q_r,i) . k_r) / sqrt(d_n + d_r)) v_i(s)``;
``MLA(h) = W_o [o_1 | ... | o_H]``.

Departures from the published description, each also under ``assumed`` in
``benchmark/configs/longcat-flash-chat-d4.json``: the rope pairs are
INTERLEAVED (dims 2i, 2i + 1 a pair, as DeepSeek-V3's inference code has
them; the config has no key for it); ``mla_scale_q_lora`` /
``mla_scale_kv_lora`` read as the constants ``a_q``, ``a_kv`` above; no
multi-token prediction module; the router and its softmax in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
F32 = jnp.float32
# keys of the configuration the reference reads
KEYS = ("hidden_size", "rms_norm_eps", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "rope_theta", "mla_scale_q_lora", "mla_scale_kv_lora",
        "moe_topk", "routed_scaling_factor", "n_routed_experts",
        "zero_expert_num", "first_expert")


def freeze(cfg):
    """The configuration's ``KEYS`` as a hashable tuple of items."""
    return tuple((k, cfg[k]) for k in KEYS)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, pos, theta):
    """``x`` (T, ..., D) rotated at positions ``pos`` (T,), INTERLEAVED:
    dims (2i, 2i + 1) are a pair turned by ``pos * theta ** (-2 i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv                    # (T, D/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _blocks(t, block):
    """(block length, number of blocks) covering ``t`` rows exactly."""
    if t <= block:
        return t, 1
    if t % block:
        raise ValueError(f"{t} rows are no multiple of the block {block}")
    return block, t // block


def attention(h, p, cfg, block=512):
    """Expanded latent attention over one whole sequence, causal, a head
    at a time and a block of queries at a time. ``p``: one attention's
    weights (``q_a``, ``q_norm_w``, ``q_b``, ``kv_a``, ``kv_norm_w``,
    ``kv_b``, ``o_proj``)."""
    t, hidden = h.shape
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    a_q = math.sqrt(hidden / rq) if cfg["mla_scale_q_lora"] else 1.0
    a_kv = math.sqrt(hidden / rkv) if cfg["mla_scale_kv_lora"] else 1.0
    pos = jnp.arange(t)
    c_q = a_q * rms_norm(h @ p["q_a"], p["q_norm_w"], eps)
    ckr = h @ p["kv_a"]
    c = a_kv * rms_norm(ckr[:, :rkv], p["kv_norm_w"], eps)
    k_r = rope(ckr[:, rkv:], pos, cfg["rope_theta"])         # (T, dr)
    b, n = _blocks(t, block)
    scale = 1.0 / math.sqrt(dn + dr)

    def head(acc, x):
        wq, wkv, wo = x             # (rq, dn+dr) (rkv, dn+dv) (dv, d)
        q = c_q @ wq
        q_n, q_r = q[:, :dn], rope(q[:, dn:], pos, cfg["rope_theta"])
        kv = c @ wkv
        k_n, v = kv[:, :dn], kv[:, dn:]

        def rows_of(i):
            rows = i * b + jnp.arange(b)
            s = (q_n[rows] @ k_n.T + q_r[rows] @ k_r.T) * scale
            s = jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = jax.lax.map(rows_of, jnp.arange(n)).reshape(t, dv)
        return acc + o @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros((t, hidden), F32), (
        jnp.moveaxis(p["q_b"].reshape(rq, heads, dn + dr), 1, 0),
        jnp.moveaxis(p["kv_b"].reshape(rkv, heads, dn + dv), 1, 0),
        p["o_proj"].reshape(heads, dv, hidden)))
    return out


def swiglu(u, gate_up, down):
    g, v = jnp.split(u @ gate_up.astype(F32), 2, axis=-1)
    return (silu(g) * v) @ down.astype(F32)


def swiglu_blocked(u, gate_up, down, block):
    """``swiglu`` a block of rows at a time (a 12,288-wide layer's
    activations of a long sequence do not fit whole)."""
    b, n = _blocks(u.shape[0], block)
    return jax.lax.map(lambda x: swiglu(x, gate_up, down),
                       u.reshape(n, b, -1)).reshape(u.shape)


def route(u, p, cfg, routing=None):
    """(sets used (T, K), weights (T, K), the reference's own sets
    (T, K), selection scores p + b (T, routed + identity)) over ALL the
    router's columns. ``routing`` = (sets (T, K), forced (T,) bool): where
    forced, the given set is used in place of the reference's own choice
    (its weights are still the reference's probabilities)."""
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    sel = probs + p["router_bias"]
    _, own = jax.lax.top_k(sel, cfg["moe_topk"])
    sets = own
    if routing is not None:
        given, forced = routing
        sets = jnp.where(forced[:, None], given, own)
    w = jnp.take_along_axis(probs, sets, axis=-1)
    return sets, w * cfg["routed_scaling_factor"], own, sel


def experts(u, p, sets, w, first, zero_from=None):
    """The HELD routed experts (``first .. first + held``) on every token,
    masked: no sort, no grouping; one expert's matrices upcast at a time.
    An assignment to a routed expert not held adds nothing; with
    ``zero_from``, one to an identity expert (id >= ``zero_from``) adds
    ``w * u``."""
    held = p["experts_gate_up"].shape[0]
    ids = first + jnp.arange(held)
    per_expert = jnp.sum(
        jnp.where(sets[:, :, None] == ids[None, None, :], w[:, :, None],
                  0.0), axis=1)                              # (T, held)

    def one(acc, x):
        gate_up, down, we = x
        return acc + we[:, None] * swiglu(u, gate_up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate_up"], p["experts_down"], per_expert.T))
    if zero_from is not None:
        out = out + jnp.sum(jnp.where(sets >= zero_from, w, 0.0),
                            axis=1)[:, None] * u
    return out


def moe(u, p, cfg, routing=None):
    """(MoE(u), sets used, own sets, p + b) of one layer."""
    sets, w, own, sel = route(u, p, cfg, routing)
    out = experts(u, p, sets, w, cfg["first_expert"],
                  cfg["n_routed_experts"])
    return out, sets, own, sel


def sub(p, prefix):
    """One sublayer's weights of a layer's dict, without the prefix."""
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def run_layer(p, x, cfg, routing=None, block=512):
    """One double layer over one whole sequence x (T, d). Returns (y,
    {"sets": the sets used, "own": the reference's own choice on this
    layer's input, "sel": p + b})."""
    with jax.default_matmul_precision(HIGHEST):
        eps = cfg["rms_norm_eps"]
        a1 = x + attention(rms_norm(x, p["in0_w"], eps), sub(p, "attn0_"),
                           cfg, block)
        u = rms_norm(a1, p["post0_w"], eps)
        m, sets, own, sel = moe(u, p, cfg, routing)
        b1 = a1 + swiglu_blocked(u, p["mlp0_gate_up"], p["mlp0_down"],
                                 block)
        a2 = b1 + attention(rms_norm(b1, p["in1_w"], eps), sub(p, "attn1_"),
                            cfg, block)
        y = a2 + swiglu_blocked(rms_norm(a2, p["post1_w"], eps),
                                p["mlp1_gate_up"], p["mlp1_down"], block) + m
        return y, {"sets": sets, "own": own, "sel": sel}


def head(x, lm_head, norm_w, cfg):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def forward(weights, ids, cfg, routing=None, block=512):
    """The whole model over one sequence ``ids`` (T,) of rows held.
    ``weights``: {"embed" (V, d), "layers" [dict], "norm_w", "lm_head"
    (d, V)}; ``routing``: None or one entry per layer (None where nothing
    is forced). Returns (logits (T, V), [info per layer])."""
    x = weights["embed"][ids]
    infos = []
    for l, p in enumerate(weights["layers"]):
        x, info = run_layer(p, x, cfg,
                            None if routing is None else routing[l], block)
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
