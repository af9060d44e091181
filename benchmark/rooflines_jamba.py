"""Operations, bytes and parameters of what a Jamba step runs: the
selective scan of its Mamba layers, the multi-query attention of its
attention layers, and its dense weights. The yardstick's own arithmetic
from the configuration file and from what each dispatch was handed
(``cu_seqlens``, ``context_lens``, ``num_seqs``); nothing here is read
from the program under test. Which layer is which comes from the
configuration's own two keys (``attn_layer_period``, ``attn_layer_offset``).
"""
from __future__ import annotations

from benchmark import rooflines
from benchmark.rooflines_hybrid import ITEM, STATE, rows_of


def layer_kinds(m):
    return ["attention"
            if l % m["attn_layer_period"] == m["attn_layer_offset"]
            else "mamba" for l in range(m["num_hidden_layers"])]


def scan_bytes(m, cu, ctx, num_seqs):
    """All Mamba layers of one dispatch, the least that must move: per
    row the state (d_state x d_inner float32) written, and read unless
    the row starts at position 0; per token x', dt and y (d_inner each)
    and B, C (d_state each) in bfloat16."""
    e = m["mamba_expand"] * m["hidden_size"]
    n = m["mamba_d_state"]
    total = 0
    for nq, c in rows_of(cu, ctx, num_seqs):
        total += (2 if c > nq else 1) * n * e * STATE
        total += nq * (3 * e + 2 * n) * ITEM
    return total * layer_kinds(m).count("mamba")


def attention_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of the attention layers of one dispatch: every
    query head against each visible key of the one K/V head
    (``rooflines.ragged_attention_work``: K and V of a row read once, q
    in and the output out), times the attention layers."""
    flops, nbytes = rooflines.ragged_attention_work(m, cu, ctx, num_seqs,
                                                    itemsize=ITEM)
    layers = layer_kinds(m).count("attention")
    return layers * flops, layers * nbytes


def dense_groups(m):
    """Every parameter by what counts it, in ``rooflines_dense.counted``'s
    groups: ``stream`` the matrices that multiply every query token (the
    Mamba projections, q/k/v/o, the SwiGLU), ``other`` the norms, taps
    and the scan's small vectors; the head is tied to the embedding."""
    h, e = m["hidden_size"], m["mamba_expand"] * m["hidden_size"]
    d = h // m["num_attention_heads"]
    state, taps, rank = (m["mamba_d_state"], m["mamba_d_conv"],
                         m["mamba_dt_rank"])
    out = {"stream": 0, "rows": 0, "float32": 0,
           "embedding": m["vocab_size"] * h, "head": 0, "experts": 0,
           "indexer": 0, "other": h}            # the final norm
    for kind in layer_kinds(m):
        out["stream"] += 3 * h * m["intermediate_size"]
        out["other"] += 2 * h                   # the layer's two norms
        if kind == "mamba":
            out["stream"] += (h * 2 * e + e * (rank + 2 * state)
                              + rank * e + e * h)
            # conv taps and bias, inner norms, dt bias, A, D
            out["other"] += (taps * e + e + rank + 2 * state + e
                             + e * state + e)
        else:
            out["stream"] += 2 * h * h + 2 * h * m["num_key_value_heads"] * d
    return out
