"""Operations and bytes of the LongCat-Flash decoder's step
(``benchmark/configs/longcat-flash-chat-d4.json``): the expert layers'
grouped products, the latent attention calls (TWO a layer) and the dense
weights. ``rooflines_moe`` reads keys this configuration does not have
(``moe_intermediate_size``, ``num_hidden_layers``, one attention a layer);
the arithmetic is the same. The yardstick's own, from the configuration
file and from what each dispatch was handed (the experts: the program's
own count of assignments to HELD routed experts and of experts hit, from
its ``engine.post`` span, so an identity pick counts nowhere; the latent
calls: ``cu_seqlens``, ``context_lens``, ``num_seqs``); nothing here
depends on what implements them.
"""
from __future__ import annotations

from benchmark import rooflines_dense

ITEM = 2        # bfloat16: weights, activations, cache entries


def expert_work(m, expert_rows, experts_hit):
    """(operations, bytes) of the held routed experts of one dispatch, all
    expert layers together. ``expert_rows``: the live rows' assignments to
    held routed experts (identity picks are not in the histogram);
    ``experts_hit``: held experts given at least one row, summed over
    layers. Operations: the three matrices of an expert, 2 a multiply-add.
    Least bytes: each hit expert's weights once, each assignment's row
    read (hidden wide) and its result written (hidden wide)."""
    d, f = m["hidden_size"], m["expert_ffn_hidden_size"]
    flops = 2 * 3 * d * f * expert_rows
    nbytes = ITEM * (experts_hit * 3 * d * f + 2 * expert_rows * d)
    return flops, nbytes


def latent_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of the latent attention calls of one dispatch,
    both attentions of every layer: per visible (query, key) pair and head
    a score over the published 576 numbers of an entry and a value product
    over its 512; each live row's entries read once at 576 numbers, the
    queries read and the outputs written."""
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
    calls = 2 * m["num_layers"]
    flops = calls * 2 * h * (key + val) * pairs
    nbytes = calls * ITEM * (entries * key + q_rows * h * (key + val))
    return flops, nbytes


def dense_groups(m):
    """Every parameter by what counts it, in ``rooflines_dense.counted``'s
    groups: ``stream`` the matrices that multiply every query token (two
    latent attentions, two dense FFNs and the float32 router a layer),
    ``experts`` the held routed experts, ``other`` the norms and the
    router's bias; embedding and head are the vocabulary rows held."""
    h = m["hidden_size"]
    attn, norms = rooflines_dense._mla(
        m, m["num_attention_heads"], m["qk_nope_head_dim"],
        m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"],
        m["q_lora_rank"])
    width = m["published"]["n_routed_experts"] + m["zero_expert_num"]
    table = m["vocab_size"] * h
    layers = m["num_layers"]
    return {"stream": layers * (2 * attn + 2 * 3 * h * m["ffn_hidden_size"]
                                + h * width),
            "rows": 0, "float32": layers * h * width, "embedding": table,
            "head": table,
            "experts": layers * m["n_routed_experts"] * 3 * h
            * m["expert_ffn_hidden_size"],
            "indexer": 0,
            "other": layers * (2 * norms + 4 * h + width) + h}
