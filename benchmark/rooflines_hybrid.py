"""Operations and bytes of what a decoder-hybrid-decoder step adds to a
dense decoder's: the selective scan of its state-space layers and the
attention calls of its window, full and cross layers. The yardstick's own
arithmetic from the configuration file and from what each dispatch was
handed (``cu_seqlens``, ``context_lens``, ``num_seqs``); nothing here is
read from the program under test. The least that must move: queries and
outputs at the published head width, keys and values once per layer that
reads them, a row's recurrent state in and out once.
"""
from __future__ import annotations

from benchmark import reference_phi4flash as ref

ITEM = 2        # bfloat16: activations, K/V
STATE = 4       # the scan's state is float32


def rows_of(cu, ctx, num_seqs):
    """[(new tokens, context length after the step)] of the live rows."""
    out = []
    for i in range(int(num_seqs)):
        n = int(cu[i + 1]) - int(cu[i])
        if n > 0:
            out.append((n, int(ctx[i])))
    return out


def kinds(m):
    n = m["num_hidden_layers"]
    return [ref.layer_kind(l, n) for l in range(n)]


def scan_bytes(m, cu, ctx, num_seqs):
    """All state-space layers of one dispatch: per row the state
    (d_state x d_inner float32) written, and read unless the row starts
    at position 0; per token x', dt and y (d_inner each) and B, C
    (d_state each) in bfloat16."""
    e = m["mamba_expand"] * m["hidden_size"]
    n = m["mamba_d_state"]
    total = 0
    for nq, c in rows_of(cu, ctx, num_seqs):
        total += (2 if c > nq else 1) * n * e * STATE
        total += nq * (3 * e + 2 * n) * ITEM
    return total * kinds(m).count("mamba")


def attention_work(m, cu, ctx, num_seqs):
    """(operations, bytes) of every attention call of one dispatch.
    Window layers: a row's queries see at most ``new + window - 1`` keys.
    The full layer: every key of the row. Cross layers: one query a row
    (the cross-decoder runs on the rows that can yield a token) over
    every key of the full layer's cache. Per query PAIR and key, the
    least: two head_dim-wide dots for the two score maps and, after the
    maps are combined, one (2 x head_dim)-wide value product, which is
    the 4 x heads x head_dim operations of plain attention."""
    h, kh = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // h
    w = m["sliding_window"]
    count = kinds(m)
    flops = nbytes = 0
    for nq, c in rows_of(cu, ctx, num_seqs):
        start = c - nq
        full_pairs = nq * start + nq * (nq + 1) // 2
        win_pairs = sum(min(start + j + 1, w) for j in range(nq))
        calls = ((count.count("window"), nq, win_pairs,
                  min(c, nq + w - 1)),
                 (count.count("full"), nq, full_pairs, c),
                 (count.count("cross"), 1, c, c))
        for layers, q_rows, pairs, keys in calls:
            flops += layers * 4 * h * d * pairs
            nbytes += layers * ITEM * (2 * keys * kh * d
                                       + 2 * q_rows * h * d)
    return flops, nbytes
