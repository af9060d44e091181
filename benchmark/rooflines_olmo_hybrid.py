"""Operations, bytes and parameters of what an Olmo Hybrid step runs: the
gated delta rule of its linear-attention layers (convolution and
recurrence), and its dense weights. The yardstick's own arithmetic from
the configuration file and from what each dispatch was handed
(``cu_seqlens``, ``context_lens``, ``num_seqs``); nothing here is read
from the program under test. Which layer is which comes from the
configuration's own ``layer_types``.
"""
from __future__ import annotations

from benchmark.rooflines_hybrid import ITEM, STATE, rows_of

CHUNK = 64      # the chunked form's tokens a chunk (arXiv:2412.06464)


def layer_kinds(m):
    return list(m["layer_types"])


def _widths(m):
    h = m["linear_num_key_heads"]
    return (h, m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel_dim"])


def chunk_flops(h, dk, dv, c=CHUNK):
    """Operations of the chunked form on one chunk of ``c`` tokens, all
    heads: K K^T and Q K^T, the triangular solve for U and W, W S and
    Q S, the masked scores times u, and the state's update (a
    multiply-add is 2)."""
    return h * (2 * 2 * c * c * dk + c * c * (dk + dv)
                + 3 * 2 * c * dk * dv + 2 * c * c * dv)


def step_flops(h, dk, dv):
    """Operations of one token's step, all heads: the decay, S^T k,
    the rank-one update and S^T q."""
    return h * 7 * dk * dv


def delta_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of all linear-attention layers of one
    dispatch, the least that must move: per row the state (dk x H dv
    float32) and the convolution's last inputs (taps - 1 of 2 H dk + H dv,
    bfloat16) written, and read unless the row starts at position 0; per
    token q and k (H dk each), v and o (H dv each), the decay's and the
    step size's inputs (H each) in bfloat16. Operations: a row of one
    token one step, any other the chunked form over its chunks."""
    h, dk, dv, taps = _widths(m)
    state = dk * h * dv * STATE + (taps - 1) * (2 * h * dk + h * dv) * ITEM
    flops = nbytes = 0
    for nq, c in rows_of(cu, ctx, num_seqs):
        nbytes += (2 if c > nq else 1) * state
        nbytes += nq * (2 * h * dk + 2 * h * dv + 2 * h) * ITEM
        flops += (step_flops(h, dk, dv) if nq == 1
                  else -(-nq // CHUNK) * chunk_flops(h, dk, dv))
    layers = layer_kinds(m).count("linear_attention")
    return layers * flops, layers * nbytes


def dense_groups(m):
    """Every parameter by what counts it, in ``rooflines_dense.counted``'s
    groups: ``stream`` the matrices whose time ``serve.dense_ms`` reads
    (the full layers' q/k/v/o under ``attn_proj``, every SwiGLU under
    ``mlp``); ``gdn`` the linear layers' projections, which run under
    ``gdn_proj`` and ``gdn_out`` (``serve.gdn_ms`` leaves them out too);
    ``head`` the untied head; ``other`` the norms, taps and the decay's
    per-head vectors."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, dk, dv, taps = _widths(m)
    conv = 2 * h * dk + h * dv
    out = {"stream": 0, "rows": 0, "float32": 0,
           "embedding": m["vocab_size"] * d, "head": m["vocab_size"] * d,
           "experts": 0, "indexer": 0, "gdn": 0, "other": d}
    for kind in layer_kinds(m):
        out["stream"] += 3 * d * f
        out["other"] += 2 * d                   # the two post-norms
        if kind == "linear_attention":
            out["gdn"] += d * conv + 2 * d * h + d * h * dv + h * dv * d
            # conv taps, the output norm, A_log and dt_bias
            out["other"] += taps * conv + dv + 2 * h
        else:
            out["stream"] += 4 * d * d
            out["other"] += 2 * d               # the QK-norms
    return out
