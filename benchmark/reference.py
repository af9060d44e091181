"""Plain references the program's outputs are held to, in NumPy and
float32, written from the definitions and independent of the program.

Today: ragged paged causal attention for one layer. The model-level
reference (a whole forward pass and loss in float32, ROADMAP D8) is the
next ``benchmark`` issue's; see PERF.md, Open questions.
"""
from __future__ import annotations

import numpy as np


def token_positions(t_total, cu, ctx, num_seqs):
    """Absolute position of each token of the packed stream: sequence i
    owns tokens cu[i]..cu[i+1], which end a context of ctx[i] tokens.
    Padding tokens get -1."""
    pos = np.full((t_total,), -1, np.int32)
    for i in range(int(num_seqs)):
        a, b = int(cu[i]), int(cu[i + 1])
        pos[a:b] = int(ctx[i]) - (b - a) + np.arange(b - a)
    return pos


def ragged_attention(q, k_new, v_new, k_cache, v_cache, block_tables, cu,
                     ctx, num_seqs, scale):
    """Causal attention of a packed stream over a paged cache, one
    sequence at a time. ``q`` (T,H,D); ``k_new``/``v_new`` (T,KH,D) are
    this step's keys and values; ``k_cache``/``v_cache``
    (blocks, block size, KH, D) hold what was cached BEFORE the step,
    found through ``block_tables`` (S, max blocks). A query sees the
    cached prefix and the new tokens up to itself; query head h reads
    key/value head h // (H / KH). Returns (T,H,D), padding rows zero."""
    t_total, heads, d = q.shape
    bs, kvh = k_cache.shape[1], k_cache.shape[2]
    rep = heads // kvh
    out = np.zeros((t_total, heads, d), np.float32)
    for i in range(int(num_seqs)):
        a, b = int(cu[i]), int(cu[i + 1])
        n, c = b - a, int(ctx[i])
        if n <= 0:
            continue
        old = c - n
        table = block_tables[i][:-(-c // bs)]
        k = k_cache[table].reshape(-1, kvh, d)[:c].astype(np.float32)
        v = v_cache[table].reshape(-1, kvh, d)[:c].astype(np.float32)
        k[old:], v[old:] = k_new[a:b], v_new[a:b]
        k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
        logits = np.einsum("qhd,khd->hqk", q[a:b].astype(np.float32),
                           k) * scale
        seen = np.arange(c)[None, :] <= (old + np.arange(n))[:, None]
        logits = np.where(seen[None], logits, -np.inf)
        p = np.exp(logits - logits.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out[a:b] = np.einsum("hqk,khd->qhd", p, v)
    return out
