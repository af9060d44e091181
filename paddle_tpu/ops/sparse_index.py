"""The learned sparse indexer of a latent-attention layer (DeepSeek-V3.2's
"lightning indexer"): which cached positions each query row attends to.

Two ops over the engine's ragged token stream (the contract of
``ops/pallas/ragged_paged_attention.py``: rows packed by slot, ``cu_seqlens``,
``context_lens``, one block table a slot):

* :func:`index_scores` — every live row against its slot's cached index
  keys, ONE 128-wide key a token in a paged array ``(blocks, block_size,
  lanes)`` beside the layer's latent pool, under the same block table:
  ``I(t, s) = sum_j w[t, j] * ReLU(q[t, j] . k[s])`` for ``s <= pos(t)``,
  float32, ``(T, MB * BS)`` indexed by the slot's LOGICAL position, ``-inf``
  where the row sees nothing (the future, another slot, padding). The
  rows' own keys are written first, by the scatter the latent entry uses.
* :func:`select_topk` — the exact ``k`` largest scores of each row as a
  ``(T, MB * BS)`` int8 mask (what ``ops/pallas/sparse_latent_attention.py``
  consumes): every visible position where at most ``k`` are visible; of
  equal scores at the boundary the LOWEST position first
  (``jax.lax.top_k``'s rule). The boundary is found by ``ops/sampling.py``'s
  threshold passes (32 compare-and-reduce passes over an order-preserving
  integer image of the scores), never by sorting a row: ``jax.lax.top_k(k=
  2048)`` over ``(rows, 32k)`` is the sort PR 30 took out of the sampler.

``impl`` as the attention op's: ``"ref"`` (plain ``jnp``: gathers each
row's whole context, small shapes only), ``"pallas"`` (the compiled
kernel of ``ops/pallas/index_scores.py``), ``"interpret"``; None picks
Pallas on a TPU and ``"ref"`` elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.index_scores import index_scores_pallas
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _resolve_impl, _token_layout, _write_kv,
)
from paddle_tpu.ops.sampling import _select

__all__ = ["index_scores", "select_topk", "selection_counts"]


def _scores_ref(q, w, cache, bt, seg, pos, valid):
    t_total = q.shape[0]
    _, bs, _ = cache.shape
    mb = bt.shape[1]
    bt_tok = bt[seg]                                         # (T, MB)
    k_seq = cache[jnp.maximum(bt_tok, 0)].reshape(t_total, mb * bs, -1)
    dots = jnp.einsum("thd,tld->thl", q, k_seq,
                      preferred_element_type=jnp.float32)
    total = jnp.einsum("th,thl->tl", w.astype(jnp.float32),
                       jax.nn.relu(dots))
    lpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]
    seen = ((lpos <= pos[:, None]) & (bt_tok >= 0).repeat(bs, axis=1)
            & valid[:, None])
    return jnp.where(seen, total, -jnp.inf)


def index_scores(q, w, k_new, index_cache, block_tables, cu_seqlens,
                 context_lens, num_seqs, *, impl=None):
    """``q`` (T, HI, DI) index queries, ``w`` (T, HI) float32 head
    weights, ``k_new`` (T, DI) the rows' own index keys (None: nothing
    is written), ``index_cache`` (blocks, block_size, DI). Returns
    (scores (T, MB * block_size) float32, index_cache')."""
    impl = _resolve_impl(impl)
    bt = jnp.asarray(block_tables).astype(jnp.int32)
    cu = jnp.asarray(cu_seqlens).astype(jnp.int32)
    ctx = jnp.asarray(context_lens).astype(jnp.int32)
    ns = jnp.asarray(num_seqs).astype(jnp.int32)
    seg, pos, valid = _token_layout(q.shape[0], bt.shape[0], cu, ctx, ns)
    if k_new is not None:
        with jax.named_scope("kv_update"):
            index_cache = _write_kv(index_cache, k_new, bt, seg, pos)
    with jax.named_scope("index_scores"):
        if impl == "ref":
            scores = _scores_ref(q, w, index_cache, bt, seg, pos, valid)
        else:
            scores = index_scores_pallas(
                q, w, index_cache, bt, cu, ctx, ns,
                interpret=(impl == "interpret"))
    return scores, index_cache


def _order_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 and 0.0, equal as floats, made one value first)."""
    x = jnp.where(x == 0, 0.0, x)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_topk(scores, k):
    """``scores`` (T, L) float32, ``-inf`` where the row sees nothing.
    Returns the (T, L) int8 mask of each row's ``k`` largest visible
    scores (all visible where at most ``k`` are; ties at the boundary:
    lowest position first)."""
    with jax.named_scope("index_select"):
        t, width = scores.shape
        visible = scores > -jnp.inf
        # invisible entries order below every score (-inf itself maps
        # above 0), so a row with fewer than k visible reads boundary 0
        key = jnp.where(visible, _order_bits(scores), jnp.uint32(0))
        kth = _select(key, 1, jnp.full((t,), k, jnp.int32), width=32)
        above = key > kth[:, None]
        tie = visible & (key == kth[:, None])
        n_tie = jnp.sum(tie, axis=-1)
        m = jnp.clip(k - jnp.sum(above, axis=-1), 0, n_tie)

        def first_ties(_):
            # the m-th tie by position = the (n_tie - m + 1)-th from the
            # top (ops/sampling.py's nucleus boundary does the same)
            at = jnp.where(tie, jnp.arange(1, width + 1, dtype=jnp.uint32),
                           jnp.uint32(0))
            last = _select(at, 1, n_tie - m + 1, width=width.bit_length())
            return tie & (at <= last[:, None])

        # a boundary value held by more positions than the set has room
        # for is the rare case: only then are the ties told apart
        kept = jax.lax.cond(jnp.any(n_tie > m), first_ties, lambda _: tie,
                            None)
        return (visible & (above | kept)).astype(jnp.int8)


def selection_counts(scores, selected, block_tables, cu_seqlens,
                     context_lens, num_seqs):
    """One layer's three counters of a step, int32 (3,): keys visible
    to the live rows, keys selected, and DISTINCT entries selected summed
    over the slots (what a gather of the union would have to read)."""
    t, width = selected.shape
    s_slots = block_tables.shape[0]
    seg, _, valid = _token_layout(t, s_slots, cu_seqlens, context_lens,
                                  num_seqs)
    sel = jnp.where(valid[:, None], selected, 0)
    union = jnp.zeros((s_slots + 1, width), jnp.int8).at[
        jnp.where(valid, seg, s_slots)].max(sel)
    return jnp.stack([
        jnp.sum((scores > -jnp.inf) & valid[:, None], dtype=jnp.int32),
        jnp.sum(sel, dtype=jnp.int32),
        jnp.sum(union[:s_slots], dtype=jnp.int32)])
