"""Operations and bytes of what a latent-attention, routed-expert decoder
step adds to a dense decoder's: the expert layers' grouped products and
the latent attention calls. The yardstick's own arithmetic from the
configuration file and from what each dispatch was handed (the experts:
the program's own count of assignments and of experts hit, from its
``engine.post`` span; the latent call: ``cu_seqlens``, ``context_lens``,
``num_seqs``); nothing here depends on what implements them.
"""
from __future__ import annotations

ITEM = 2        # bfloat16: weights, activations, cache entries


def expert_work(m, expert_rows, experts_hit):
    """(operations, bytes) of the routed experts of one dispatch, all
    expert layers together. ``expert_rows``: assignments of the live rows
    (rows x experts per token, summed over layers); ``experts_hit``:
    experts given at least one row, summed over layers. Operations: the
    three matrices of an expert, 2 a multiply-add. Least bytes: each hit
    expert's weights once, each assignment's row read (hidden wide) and
    its result written (hidden wide)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2 * 3 * d * f * expert_rows
    nbytes = ITEM * (experts_hit * 3 * d * f + 2 * expert_rows * d)
    return flops, nbytes


def latent_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of the latent attention calls of one dispatch,
    every layer: per visible (query, key) pair and head a score over the
    published 576 numbers of an entry and a value product over its 512;
    each live row's entries read once at 576 numbers (whatever lanes the
    cache pads them to), the queries read and the outputs written."""
    h = m["num_attention_heads"]
    key = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    val = m["kv_lora_rank"]
    pairs = q_rows = entries = 0
    for i in range(int(num_seqs)):
        n = int(cu[i + 1]) - int(cu[i])
        c = int(ctx[i])
        if n <= 0:
            continue
        pairs += n * (c - n) + n * (n + 1) // 2
        q_rows += n
        entries += c
    layers = m["num_hidden_layers"]
    flops = layers * 2 * h * (key + val) * pairs
    nbytes = layers * ITEM * (entries * key + q_rows * h * (key + val))
    return flops, nbytes
