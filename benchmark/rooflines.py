"""What the algorithms need: operations and bytes computed from shapes,
and the table of peaks. The yardstick's own arithmetic; nothing here
is read from the program under test.

Conventions: a multiply-add is 2 operations; recomputed operations do
not count; the embedding table is a gather, not a matmul, and is left
out of the matmul parameters; ``lm_head`` is a matmul and is counted.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


def head_dim(m):
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def matmul_params(m):
    """Parameters that sit in matrix multiplications of a dense
    Llama-block decoder: q/k/v/o, the three MLP matrices, per layer,
    plus ``lm_head``. The embedding table and the norms are left out."""
    h, d = m["hidden_size"], head_dim(m)
    q = h * m["num_attention_heads"] * d
    kv = 2 * h * m["num_key_value_heads"] * d
    o = m["num_attention_heads"] * d * h
    mlp = 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (q + kv + o + mlp) + h * m["vocab_size"]


def total_params(m):
    """Every parameter, for footprints: matmuls, embedding, norms."""
    extra = m["vocab_size"] * m["hidden_size"] if not m.get(
        "tie_word_embeddings") else 0
    norms = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
    return matmul_params(m) + extra + norms


def causal_attention_flops_fwd(m, batch, seq):
    """QK^T and PV over the causal half: 2 matmuls x 2 ops x
    b*H*s*s*d / 2, per layer, summed over layers."""
    return (2 * batch * m["num_attention_heads"] * seq * seq * head_dim(m)
            * m["num_hidden_layers"])


def causal_attention_flops_train(m, batch, seq):
    """Forward plus backward (twice the forward: dV, dP, dQ, dK)."""
    return 3 * causal_attention_flops_fwd(m, batch, seq)


def flash_bytes_train(m, batch, seq, itemsize=2):
    """The least HBM traffic of the three flash kernels of one step:
    forward reads q,k,v and writes o; the backward pair reads q,k,v,o,dO
    and writes dq,dk,dv (K/V at the query heads' count: the model
    repeats them before the kernel)."""
    one = batch * seq * m["num_attention_heads"] * head_dim(m) * itemsize
    return (4 + 8) * one * m["num_hidden_layers"]


def train_flops_per_step(m, batch, seq):
    """Model FLOPs of one training step: 6 per matmul parameter per
    token, plus causal attention forward and backward."""
    return (6 * matmul_params(m) * batch * seq
            + causal_attention_flops_train(m, batch, seq))


def ragged_attention_work(m, cu, ctx, num_seqs, itemsize=2):
    """(operations, bytes) one layer's ragged paged attention needs for
    one dispatch: sequence i brings ``cu[i+1]-cu[i]`` query tokens that
    end a context of ``ctx[i]`` tokens; query j sees the cached prefix
    and the new tokens up to itself. Bytes: each sequence's K and V
    read once, q read and the output written once."""
    heads, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                     head_dim(m))
    pairs = q_tokens = kv_tokens = 0
    for i in range(int(num_seqs)):
        n = int(cu[i + 1]) - int(cu[i])
        c = int(ctx[i])
        if n <= 0:
            continue
        pairs += n * (c - n) + n * (n + 1) // 2
        q_tokens += n
        kv_tokens += c
    flops = 4 * heads * d * pairs
    nbytes = (2 * kv_tokens * kvh * d + 2 * q_tokens * heads * d) * itemsize
    return flops, nbytes


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
