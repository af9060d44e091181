"""Plain reference of the dots3-note-prev decoder (catalog row
``dots3-note-prev``, ``model_type`` ``dots3_note``): the forward pass of
ONE sequence in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No cache, no kernels, no
batching. Attention is in the EXPANDED form (per-head keys and values from
the latent), the index scores are a dense (query, key) matrix cut by
``jax.lax.top_k``, the experts are a masked loop over the ones held.
Weights are plain dicts of ``[in, out]`` matrices (``y = x @ W``) under the
program's names; the model is run layer by layer (``run_layer``), attention
a head at a time and a block of queries at a time, the dense FFN a block of
rows at a time and the experts one at a time (upcast inside the loop), so
that a 32k history fits beside the served weights.

Layer l: ``u = RMSNorm(x)``, ``h = x + Attn_l(u)``, ``y = h +
FFN_l(RMSNorm(h))``.

Attention of a FULL layer (``layer_types[l] == "full_attention"``), H = 128
heads, d_n = 128, d_r = 64, d_v = 128, r_q = 1024, r_kv = 512, rope base
8e7: ``c_q = a_q RMSNorm(W_qa u)``; ``[q_n,h | q_r,h] = W_qb,h c_q``;
``[c_raw | k_raw] = W_kva u``; ``c = a_kv RMSNorm(c_raw)``; ``k_r =
RoPE(k_raw)`` (one a token, shared by the heads); ``k_n,h = W_UK,h c``,
``v_h = W_UV,h c``; ``o_h(t) = sum_{s in S_t} softmax_{S_t}((q_n,h . k_n,h(s)
+ RoPE(q_r,h) . k_r(s)) / sqrt(192)) v_h(s)``; ``g = sigmoid(W_g u)`` (one
scalar a head); ``Attn(u) = W_o [g_1 o_1 | ... | g_H o_H]``.

The indexer that gives ``S_t`` (DeepSeek-V3.2-Exp's lightning indexer, 64
heads of 128): ``q^I_j = RoPE_64(W^I_q,j c_q)``, ``k^I = RoPE_64(LayerNorm(
W^I_k u))`` (ONE a token), ``w = W^I_w u / sqrt(64) / sqrt(128)``;
``I(t, s) = sum_j w_tj ReLU(q^I_tj . k^I_s)`` for ``s <= t``; ``S_t`` = the
``index_topk`` positions of largest ``I(t, .)`` (of equal scores the lower
position first), every visible position while ``t < index_topk``.

A SLIDING layer is the same latent attention with the ``swa_*`` sizes (H =
64, d_n = 192, r_kv = 1024, rope base 5e4, scale 1/sqrt(256)), no indexer,
``S_t`` = the positions in ``(t - sliding_window_size, t]``.

FFN, layer 0: ``W_down(SiLU(W_gate u) * W_up u)``. Layers 1..: ``s =
sigmoid(W_r u)`` over ALL ``router`` columns (256); the 8 largest of ``s +
b`` are the set K; ``w_e = s_e / (sum_K s + 1e-20) * routed_scaling_factor``;
``FFN(u) = sum_{e in K, e held} w_e E_e(u) + Shared(u)``: what the experts
this chip does not hold would add is left out, as in the program.

Departures from the published model, each also under ``assumed`` in
``benchmark/configs/dots3-note-prev-d5.json``: ``apply_mla_qkv_lora_rescale``
read as the constants ``a_q = sqrt(hidden / r_q)``, ``a_kv = sqrt(hidden /
r_kv)`` after the two latent norms; both gate types ``headwise`` read as
``sigmoid`` of a linear map of ``u``, one scalar a head, before ``W_o``; the
indexer's rope on the FIRST ``qk_rope_head_dim`` lanes of its 128 with the
full layers' base, its key's LayerNorm with weight and bias at eps 1e-6, no
Hadamard rotation and no float8 (the rotation is orthogonal: in exact
arithmetic it changes no score); half-split rope layout; float32 router.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"
F32 = jnp.float32
INDEX_NORM_EPS = 1e-6
# keys of the configuration the reference reads
KEYS = ("hidden_size", "rms_norm_eps", "layer_types", "first_k_dense_replace",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "q_lora_rank", "kv_lora_rank", "rope_theta",
        "swa_num_attention_heads", "swa_qk_nope_head_dim",
        "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_q_lora_rank",
        "swa_kv_lora_rank", "swa_rope_theta", "sliding_window_size",
        "index_n_heads", "index_head_dim", "index_topk",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "apply_mla_qkv_lora_rescale", "first_expert")


def freeze(cfg):
    """The configuration's ``KEYS`` as a hashable tuple of items."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in KEYS)


def layer_kind(l, cfg):
    """``(attention kind, ffn kind)`` of layer ``l``."""
    attn = "full" if cfg["layer_types"][l] == "full_attention" else "sliding"
    return attn, ("dense" if l < cfg["first_k_dense_replace"] else "moe")


def attn_dims(cfg, attn):
    pre = "" if attn == "full" else "swa_"
    return {k: cfg[pre + name] for k, name in (
        ("heads", "num_attention_heads"), ("dn", "qk_nope_head_dim"),
        ("dr", "qk_rope_head_dim"), ("dv", "v_head_dim"),
        ("rq", "q_lora_rank"), ("rkv", "kv_lora_rank"),
        ("theta", "rope_theta"))}


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, pos, theta):
    """``x`` (T, ..., D) rotated at positions ``pos`` (T,): dims (i,
    i + D/2) are a pair turned by ``pos * theta ** (-2 i / D)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv                    # (T, D/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_first(x, pos, theta, n):
    """RoPE on the first ``n`` lanes of ``x``, the rest as they are."""
    return jnp.concatenate([rope(x[..., :n], pos, theta), x[..., n:]], -1)


def _blocks(t, block):
    """(block length, number of blocks) covering ``t`` rows exactly."""
    if t <= block:
        return t, 1
    if t % block:
        raise ValueError(f"{t} rows are no multiple of the block {block}")
    return block, t // block


def index_scores(u, c_q, p, cfg, rows):
    """``I(t, s)`` for the queries ``rows`` (B,) against every position of
    the sequence: (B, T) float32, ``-inf`` where ``s > t``."""
    t = u.shape[0]
    hi, di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])
    theta = cfg["rope_theta"]
    pos = jnp.arange(t)
    k = layer_norm(u @ p["idx_k"], p["idx_k_norm_w"], p["idx_k_norm_b"],
                   INDEX_NORM_EPS)
    k = rope_first(k, pos, theta, dr)                       # (T, di)
    q = (c_q[rows] @ p["idx_q"]).reshape(rows.shape[0], hi, di)
    q = rope_first(q, rows, theta, dr)
    w = (u[rows] @ p["idx_w"]) / math.sqrt(hi) / math.sqrt(di)   # (B, hi)

    def one(acc, x):
        qj, wj = x
        return acc + wj[:, None] * jax.nn.relu(qj @ k.T), None

    total, _ = jax.lax.scan(one, jnp.zeros((rows.shape[0], t), F32),
                            (jnp.moveaxis(q, 1, 0), w.T))
    return jnp.where(pos[None, :] <= rows[:, None], total, -jnp.inf)


def top_positions(scores, rows, k):
    """The selected set of each query as a (B, T) bool mask: the ``k``
    largest visible scores (``jax.lax.top_k``: of equal ones the lower
    position first), every visible position where fewer are visible."""
    b, t = scores.shape
    _, idx = jax.lax.top_k(scores, min(k, t))
    chosen = jnp.zeros((b, t), bool).at[jnp.arange(b)[:, None], idx].set(
        True)
    return chosen & (jnp.arange(t)[None, :] <= rows[:, None])


def select(u, c_q, p, cfg, block):
    """The reference's own selection over the whole sequence, a (T, T)
    bool mask, a block of queries at a time."""
    t = u.shape[0]
    b, n = _blocks(t, block)

    def one(i):
        rows = i * b + jnp.arange(b)
        return top_positions(index_scores(u, c_q, p, cfg, rows), rows,
                             cfg["index_topk"])

    return jax.lax.map(one, jnp.arange(n)).reshape(t, t)


def attention(u, p, cfg, attn, index=None, block=512):
    """Expanded latent attention over one whole sequence, a head at a
    time. Returns (out (T, d), info): for a full layer ``info`` holds the
    reference's OWN index scores and selection at the forced rows
    (``index`` = (mask (n, T) bool, start): rows ``start .. start + n``
    attend to the given sets in place of the reference's own), or its whole
    (T, T) selection when nothing is forced."""
    t, hidden = u.shape
    a = attn_dims(cfg, attn)
    h, dn, dr, dv = a["heads"], a["dn"], a["dr"], a["dv"]
    eps = cfg["rms_norm_eps"]
    rescale = cfg["apply_mla_qkv_lora_rescale"]
    a_q = math.sqrt(hidden / a["rq"]) if rescale else 1.0
    a_kv = math.sqrt(hidden / a["rkv"]) if rescale else 1.0
    pos = jnp.arange(t)
    c_q = a_q * rms_norm(u @ p["q_a"], p["q_norm_w"], eps)
    ckr = u @ p["kv_a"]
    c = a_kv * rms_norm(ckr[:, :a["rkv"]], p["kv_norm_w"], eps)
    k_r = rope(ckr[:, a["rkv"]:], pos, a["theta"])           # (T, dr)
    gate = jax.nn.sigmoid(u @ p["gate"])                     # (T, H)
    b, n = _blocks(t, block)
    info = {}
    if attn == "full":
        sel = select(u, c_q, p, cfg, block)
        if index is None:
            info["idx_own"] = sel
        else:
            given, start = index
            rows = start + jnp.arange(given.shape[0])
            scores = index_scores(u, c_q, p, cfg, rows)
            info.update(idx_scores=scores, idx_own=top_positions(
                scores, rows, cfg["index_topk"]))
            sel = jax.lax.dynamic_update_slice(sel, given, (start, 0))

        def visible(rows):
            return jax.lax.dynamic_slice(sel, (rows[0], 0), (b, t))
    else:
        w = cfg["sliding_window_size"]

        def visible(rows):
            return ((pos[None, :] <= rows[:, None])
                    & (pos[None, :] > rows[:, None] - w))

    scale = 1.0 / math.sqrt(dn + dr)

    def head(acc, x):
        wq, wkv, wo, g = x          # (rq, dn+dr) (rkv, dn+dv) (dv, d) (T,)
        q = c_q @ wq
        q_n, q_r = q[:, :dn], rope(q[:, dn:], pos, a["theta"])
        kv = c @ wkv
        k_n, v = kv[:, :dn], kv[:, dn:]

        def rows_of(i):
            rows = i * b + jnp.arange(b)
            s = (q_n[rows] @ k_n.T + q_r[rows] @ k_r.T) * scale
            s = jnp.where(visible(rows), s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = jax.lax.map(rows_of, jnp.arange(n)).reshape(t, dv)
        return acc + (g[:, None] * o) @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros((t, hidden), F32), (
        jnp.moveaxis(p["q_b"].reshape(a["rq"], h, dn + dr), 1, 0),
        jnp.moveaxis(p["kv_b"].reshape(a["rkv"], h, dn + dv), 1, 0),
        p["o_proj"].reshape(h, dv, hidden), gate.T))
    return out, info


def swiglu(u, gate_up, down):
    g, v = jnp.split(u @ gate_up, 2, axis=-1)
    return (silu(g) * v) @ down


def swiglu_blocked(u, gate_up, down, block):
    """``swiglu`` a block of rows at a time (a 13,824-wide layer's
    activations of a long sequence do not fit whole)."""
    b, n = _blocks(u.shape[0], block)
    return jax.lax.map(lambda x: swiglu(x, gate_up, down),
                       u.reshape(n, b, -1)).reshape(u.shape)


def route(u, p, cfg, routing=None):
    """(sets used (T, K), weights (T, K), the reference's own sets
    (T, K), selection scores s + b (T, E)) over ALL the router's columns.
    ``routing`` = (sets (T, K), forced (T,) bool): where forced, the given
    set is used in place of the reference's own choice (its weights are
    still the reference's scores)."""
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


def experts(u, p, sets, w, first):
    """The HELD experts (``first .. first + held``) on every token,
    masked: no sort, no grouping; one expert's matrices upcast at a time
    (they may be handed over in the dtype they are served in). An
    assignment to an expert not held adds nothing."""
    held = p["experts_gate_up"].shape[0]
    ids = first + jnp.arange(held)
    per_expert = jnp.sum(
        jnp.where(sets[:, :, None] == ids[None, None, :], w[:, :, None],
                  0.0), axis=1)                              # (T, held)

    def one(acc, x):
        gate_up, down, we = x
        return acc + we[:, None] * swiglu(u, gate_up.astype(F32),
                                          down.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["experts_gate_up"], p["experts_down"], per_expert.T))
    return out


def run_layer(kind, p, x, cfg, routing=None, index=None, block=512):
    """One layer over one whole sequence x (T, d); ``kind`` as
    ``layer_kind`` gives it. Returns (y, info): ``attention``'s info, and
    for an expert layer also {"sets": the sets used, "own": the
    reference's own choice on this layer's input, "sel": s + b}."""
    attn, ffn = kind
    with jax.default_matmul_precision(HIGHEST):
        eps = cfg["rms_norm_eps"]
        mix, info = attention(rms_norm(x, p["norm1_w"], eps), p, cfg, attn,
                              index=index, block=block)
        h = x + mix
        u = rms_norm(h, p["norm2_w"], eps)
        if ffn == "dense":
            return h + swiglu_blocked(u, p["gate_up"], p["down"],
                                      block), info
        sets, w, own, sel = route(u, p, cfg, routing)
        y = experts(u, p, sets, w, cfg["first_expert"]) + swiglu(
            u, p["shared_gate_up"], p["shared_down"])
        info.update(sets=sets, own=own, sel=sel)
        return h + y, info


def head(x, lm_head, norm_w, cfg):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm_w, cfg["rms_norm_eps"]) @ lm_head


def forward(weights, ids, cfg, routing=None, index=None, block=512):
    """The whole model over one sequence ``ids`` (T,). ``weights``:
    {"embed" (V, d), "layers" [dict], "norm_w", "lm_head" (d, V)};
    ``routing`` / ``index``: None or one entry per layer (None where
    nothing is forced). Returns (logits (T, V), [info per layer])."""
    x = weights["embed"][ids]
    infos = []
    for l, p in enumerate(weights["layers"]):
        x, info = run_layer(layer_kind(l, cfg), p, x, cfg,
                            None if routing is None else routing[l],
                            None if index is None else index[l], block)
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


def index_dispute(scores, own, other):
    """Two selections (n, T) bool of the same queries under the
    reference's own index scores (n, T): per query (positions in exactly
    one of the two sets, the widest gap between a disputed position's
    score and the smallest score the reference's own set holds, over the
    spread (standard deviation) of the query's visible scores). A
    selection that differs only in near-ties at the boundary reads near 0;
    one that holds a position from the future reads ``inf``."""
    differ = own ^ other
    visible = jnp.isfinite(scores)
    n_vis = jnp.maximum(jnp.sum(visible, axis=1), 1)
    mean = jnp.sum(jnp.where(visible, scores, 0.0), axis=1) / n_vis
    var = jnp.sum(jnp.where(visible, (scores - mean[:, None]) ** 2, 0.0),
                  axis=1) / n_vis
    edge = jnp.min(jnp.where(own, scores, jnp.inf), axis=1)
    gap = jnp.max(jnp.where(differ, jnp.abs(scores - edge[:, None]), 0.0),
                  axis=1)
    return jnp.sum(differ, axis=1), gap / jnp.sqrt(var + 1e-30)
