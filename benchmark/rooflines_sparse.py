"""Operations and bytes of what a sparse-indexed, windowed latent decoder
step adds (``paddle_tpu/models/dots3.py``): the indexer's scores, the
attention over the selected keys, the windowed latent attention. The
yardstick's own arithmetic from the configuration file and from what each
dispatch was handed (the program's own counts on its ``engine.post`` span:
``index_visible``, ``index_selected``, ``index_union``, each summed over
the full layers; ``cu_seqlens``, ``context_lens``, ``num_seqs``); nothing
here depends on what implements them, and every count is a LEAST: the
operations of the pairs the mathematics needs, each byte once.
"""
from __future__ import annotations

ITEM = 2        # bfloat16: weights, activations, cache entries


def layer_counts(m):
    """(full layers, sliding layers) of the configuration as run."""
    full = sum(1 for t in m["layer_types"] if t == "full_attention")
    return full, len(m["layer_types"]) - full


def rows_and_keys(cu, ctx, num_seqs):
    """(live query rows, live keys = the slots' context lengths summed)
    of one dispatch."""
    rows = keys = 0
    for i in range(int(num_seqs)):
        n = int(cu[i + 1]) - int(cu[i])
        if n > 0:
            rows += n
            keys += int(ctx[i])
    return rows, keys


def index_work(m, index_visible, cu, ctx, num_seqs):
    """(operations, bytes) of the index scores of one dispatch, all full
    layers: per visible (query, key) pair one ``index_head_dim``-wide
    product a head, 2 a multiply-add (``index_visible`` is the program's
    count, summed over the full layers); each live index key read once a
    full layer, the queries read and the scores of the visible pairs
    written (float32)."""
    full, _ = layer_counts(m)
    heads, width = m["index_n_heads"], m["index_head_dim"]
    rows, keys = rows_and_keys(cu, ctx, num_seqs)
    flops = 2 * heads * width * index_visible
    nbytes = (full * ITEM * (keys * width + rows * heads * width)
              + 4 * index_visible)
    return flops, nbytes


def sparse_work(m, index_selected, index_union, cu, ctx, num_seqs):
    """(operations, bytes) of the full layers' attention over the
    SELECTED keys of one dispatch: per selected (query, key) pair and
    head a score over the entry's published numbers (``kv_lora_rank`` +
    ``qk_rope_head_dim``) and a value product over its ``kv_lora_rank``;
    each DISTINCT selected entry read once (``index_union``: summed over
    slots and full layers), the queries read and the outputs written.
    Whatever implements the call reads at least that."""
    full, _ = layer_counts(m)
    h = m["num_attention_heads"]
    key = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    val = m["kv_lora_rank"]
    rows, _ = rows_and_keys(cu, ctx, num_seqs)
    flops = 2 * h * (key + val) * index_selected
    nbytes = ITEM * (index_union * key + full * rows * h * (key + val))
    return flops, nbytes


def window_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of the sliding layers' latent attention of one
    dispatch: per (query, key) pair inside the window and head a score
    over the ``swa_`` entry's numbers and a value product over its rank;
    each entry that some row of the slot sees read once, the queries read
    and the outputs written."""
    _, sliding = layer_counts(m)
    h, window = m["swa_num_attention_heads"], m["sliding_window_size"]
    key = m["swa_kv_lora_rank"] + m["swa_qk_rope_head_dim"]
    val = m["swa_kv_lora_rank"]
    pairs = rows = entries = 0
    for i in range(int(num_seqs)):
        n = int(cu[i + 1]) - int(cu[i])
        c = int(ctx[i])
        if n <= 0:
            continue
        pairs += sum(min(c - n + j + 1, window) for j in range(n))
        rows += n
        entries += min(c, window + n - 1)
    flops = sliding * 2 * h * (key + val) * pairs
    nbytes = sliding * ITEM * (entries * key + rows * h * (key + val))
    return flops, nbytes
