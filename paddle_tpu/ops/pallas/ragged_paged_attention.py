"""Ragged paged attention: one kernel over a concatenated token stream.

Reference capability: the serving hot path the reference covers with fused
CUDA block-attention kernels; the TPU-native design follows "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU"
(arxiv 2604.15464) — prefill and decode rows of a continuous batch are
packed into ONE unpadded token stream and attended in a single invocation
against the paged KV cache, so a mixed step has exactly one compiled
shape: (token_budget, num_seq_slots).

Contract (all data-level jnp arrays):

* ``q``:            (T, H, D)   new-token queries, ragged-packed; rows in
                                [cu_seqlens[i], cu_seqlens[i+1]) belong to
                                sequence slot i; rows >= cu_seqlens[num_seqs]
                                are padding.
* ``k_new/v_new``:  (T, KH, D)  new K/V for the same rows (GQA: KH <= H).
* ``key_cache/value_cache``: (num_blocks, block_size, KH, D) paged cache,
                    or FOLDED, (num_blocks, block_size, KH*D): a token's
                    heads side by side on the lane axis. Mosaic tiles the
                    last two dims, so it cannot slice a page of a 4-D
                    cache whose KH is no multiple of 8 (and XLA pads such
                    a cache in HBM); folded, any KH with D % 128 == 0
                    compiles and a head is a static lane slice.
* ``block_tables``: (S, MB) int32 physical block ids per slot (-1 pads).
* ``cu_seqlens``:   (S+1,) int32 exclusive prefix sum of per-slot new-token
                    counts (cu_seqlens[0] == 0).
* ``context_lens``: (S,) int32 total tokens in cache per slot AFTER this
                    step's new tokens are written (prefix + new).
* ``num_seqs``:     int32 scalar — live slots; trailing slots are padding.

The latent call (``value_cache=None, v_lanes=n``; multi-head latent
attention in its absorbed form): ONE cache ``(num_blocks, block_size, W)``
whose W-lane entry is every query head's key and whose first ``n`` lanes
are its value. ``q`` is (T, H, W), ``k_new`` (T, W) and ``v_new`` None,
the output (T, H, n); ``window=`` as for a K/V window pool. This entry
point hands it on: the latent calls have a kernel of their own
(``sparse_latent_attention.py``: a row's heads side by side on the row
axis, page groups of 512 tokens), and ``_ragged_kernel`` below is the K/V
kernel only. In the device trace the latent call is
``ragged_paged_attention`` too, under a window
``ragged_window_latent_attention``.

Returns ``(out (T, H, D), key_cache', value_cache')``: new K/V scattered
into their paged slots (functional update — in-place on TPU is buffer
donation at the jit boundary), and each query row attends causally to its
sequence's cache prefix up to and including its own absolute position.
A decode row is simply a 1-token sequence (cu delta 1, context > 1); a
prefill chunk is an n-token sequence whose positions start mid-context —
both are the same code path, which is what makes chunked prefill free.

Two implementations, shape-identical:

* ``_ragged_attend_ref`` — pure jnp gather/einsum. The semantics oracle
  and the path every non-TPU backend takes. It materializes each token's
  whole (MB*BS, KH, D) context, so it is for small shapes only.
* ``_ragged_attend_pallas`` — Pallas TPU kernel whose work follows the
  live (q tile, slot, page group) triples, not the batch's capacity. q
  and out are the stream ``(T * H, D)``: a row's H query heads side by
  side on the row axis (free reshapes), one product row a (row, head).
  The grid is the stream's tiles of ``_tile_rows`` rows (128, or fewer so
  that a K/V head's product stays within ``_PRODUCT_ROWS``: 64 at 20
  query heads a K/V head); the caches stay in HBM. Inside a tile the
  kernel walks, from the scalar-prefetched cu_seqlens/context_lens/
  block_tables, the contiguous range of slots that have rows in it and,
  per slot, its pages (from page 0, or under a window the page of the
  first row's oldest visible key) up to the causal bound of its last row
  there, in page groups of ``_GROUP_TOKENS`` (512) tokens, under a window
  about half of it (``_group_tokens``). A group is always fetched whole:
  ``pages`` async copies of K and of V (past the slot's last live page
  the table's clamped entries, which the causal bound masks) into a
  double-buffered VMEM scratch in the cache's own layout, ONE wait for
  each buffer's bytes, the next group (or the next slot's first) in
  flight while the present one is computed. A slot whose product rows
  fit one aligned window of a row's heads (a decode row, a chunk's last
  row) computes on that window: one product a K/V head, each product row
  masked to its own head's keys; any other slot on the whole tile, of one
  K/V head in one product, of more in one product a K/V head over its
  ``rep`` query heads' rows (strided reads of the stream), the other
  slots' rows masked. Online-softmax state a product row in VMEM
  scratch. Tiles past the last token do nothing but zero their output.
  Compiled, it needs head_dim % 128 == 0 and kv_heads * itemsize >= 4
  per shard.

Selection: ``impl=None`` reads ``PADDLE_RAGGED_ATTN_IMPL``, else picks
``"pallas"`` on a TPU backend and ``"ref"`` elsewhere. ``"pallas"``
always means the compiled kernel (lowering it for a CPU raises);
``"interpret"`` runs the kernel through the Pallas interpreter and
happens only when asked for by name (slow, test-only).

Under a device mesh the whole op runs per head-shard inside
``jax.shard_map`` when the tracing engine has declared the head axis
(``kernel_mesh``): GSPMD refuses to partition a Mosaic kernel.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from paddle_tpu.ops.pallas.common import declared, mxu_dot

_VMEM = pltpu.VMEM
_NEG_INF = -1e30

__all__ = ["ragged_paged_attention"]


# ---------------------------------------------------------------------------
# shared prelude: token layout + cache scatter
# ---------------------------------------------------------------------------
def _token_layout(t_total, s_slots, cu, ctx, num_seqs):
    """Per-token (segment id, absolute position, validity) for the packed
    stream. Padding tokens get pos == -1."""
    t = jnp.arange(t_total, dtype=jnp.int32)
    seg = jnp.clip(jnp.searchsorted(cu, t, side="right") - 1,
                   0, s_slots - 1).astype(jnp.int32)
    valid = (t < cu[num_seqs]) & (seg < num_seqs)
    nq = cu[seg + 1] - cu[seg]
    pos = ctx[seg] - nq + (t - cu[seg])
    pos = jnp.where(valid & (pos >= 0), pos, -1)
    return seg, pos, valid


def _write_kv(cache, new, block_tables, seg, pos):
    """Scatter packed new K/V rows into their paged slots; pos == -1 rows
    (and rows whose block-table entry is -1) scatter out of range and are
    DROPPED — routing them to slot 0 would clobber real cached tokens."""
    bs = cache.shape[1]
    blk = jnp.where(pos >= 0, pos // bs, 0)
    off = jnp.where(pos >= 0, pos % bs, 0)
    entry = block_tables[seg, blk]                       # (T,)
    valid = (pos >= 0) & (entry >= 0)
    flat = jnp.maximum(entry, 0) * bs + off
    cache_flat = cache.reshape(-1, *cache.shape[2:])
    fi = jnp.where(valid, flat, cache_flat.shape[0])
    if cache.ndim == 3:                   # folded: (T, KH, D) -> (T, KH*D)
        new = new.reshape(new.shape[0], -1)
    cache_flat = cache_flat.at[fi].set(new.astype(cache.dtype),
                                       mode="drop")
    return cache_flat.reshape(cache.shape)


# ---------------------------------------------------------------------------
# reference implementation (semantics oracle; the non-TPU path)
# ---------------------------------------------------------------------------
def _ragged_attend_ref(q, kc, vc, bt, ctx, seg, pos, valid, scale,
                       window=None, v_lanes=None, selected=None):
    # ``selected`` (T, MB * BS), a mask by logical position of the keys a
    # row may attend to among those it causally sees: no call of this
    # module passes it; it makes this the oracle and the CPU route of
    # ``sparse_latent_attention.py``
    t_total, h, d = q.shape
    if v_lanes is not None:           # latent: the entry is key and value
        kc = kc[:, :, None, :]
        vc = kc[..., :v_lanes]
    elif kc.ndim == 3:                                   # folded heads
        kc = kc.reshape(*kc.shape[:2], -1, d)
        vc = vc.reshape(*vc.shape[:2], -1, d)
    nb, bs, kh, _ = kc.shape
    mb = bt.shape[1]
    bt_tok = bt[seg]                                     # (T, MB)
    safe = jnp.maximum(bt_tok, 0)
    k_seq = kc[safe].reshape(t_total, mb * bs, kh, d)
    v_seq = vc[safe].reshape(t_total, mb * bs, kh, vc.shape[-1])
    if kh != h:
        rep = h // kh
        k_seq = jnp.repeat(k_seq, rep, axis=2)
        v_seq = jnp.repeat(v_seq, rep, axis=2)
    logits = jnp.einsum("thd,tlhd->thl", q, k_seq) * scale
    lpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]
    att = ((lpos <= pos[:, None])
           & (bt_tok >= 0).repeat(bs, axis=1)
           & valid[:, None])                             # (T, L)
    if window is not None:
        att = att & (lpos > pos[:, None] - window)
    if selected is not None:
        att = att & (selected != 0)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, logits.dtype)
    logits = jnp.where(att[:, None, :], logits, neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("thl,tlhe->the", probs.astype(v_seq.dtype), v_seq)
    # where, not multiply: padded q rows may be NaN and NaN * 0 == NaN
    return jnp.where(valid[:, None, None], out, 0)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
# cached tokens a page group (under a window about half of it: _group_tokens),
# and the most rows a K/V head's product of a q tile takes (a tile's stream
# rows x its ``rep`` query heads): the latent kernel's sizes
_GROUP_TOKENS = 512
_PRODUCT_ROWS = 2048


def _group_tokens(window):
    """Cached tokens a page group. Under a window a tile of n rows sees
    ``window + n - 1`` keys wherever it stands: groups of half the window
    waste less of each product than 512 would (the latent kernel's rule)."""
    if window is None:
        return _GROUP_TOKENS
    return max(128, min(_GROUP_TOKENS, 1 << ((window // 2).bit_length() - 1)))


def _tile_rows(t, rep):
    """Stream rows a q tile: a power of two from 16 to 128, at most ``t``
    (unless that is under 16) and at most ``_PRODUCT_ROWS`` product rows a
    K/V head (20 query heads on one K/V head: 64 rows)."""
    tile = 128
    while tile > 16 and (tile > t or tile * rep > _PRODUCT_ROWS):
        tile //= 2
    return tile


def _rows_reader(rows, k):
    """``read(h)``: rows ``h, h + k, h + 2k, ...`` of the 2-D ref ``rows``
    ``(n * k, d)`` as an ``(n, d)`` matrix, one strided load. Rows of 16
    bits share a 32-bit sublane word with the next row: there row ``h`` is
    one half of every ``k / 2``-th word of the ``(n * k / 2, d)`` view."""
    nk, d = rows.shape
    n = nk // k
    if rows.dtype.itemsize != 2 or k % 2:
        return lambda h: rows[pl.ds(h, n, stride=k), :]
    words = rows.bitcast(jnp.uint32)

    def read(h):
        w = words[pl.ds(h // 2, n, stride=k // 2), :]
        w = w & jnp.uint32(0xFFFF0000) if h % 2 else w << 16
        return pltpu.bitcast(w, jnp.float32).astype(rows.dtype)
    return read


def _head_reader(buf, d):
    """``read(g)``: KV head ``g`` of a fetched page group ``buf`` as a
    (P*BS, d) matrix. A folded group (P, BS, KH*D) keeps its heads side
    by side on lanes: a head is a static ``d``-wide lane slice. Else
    ``buf`` is (P, BS, KH, D). The pages keep the cache's own
    layout (folding heads onto lanes would re-tile the whole cache every
    call), so a head is a static index on the sublane axis, and of a
    16-bit cache a strided read of the (P*BS*KH, D) view
    (``_rows_reader``)."""
    if len(buf.shape) == 3:
        p, bs, _ = buf.shape
        return lambda g: buf[:, :, g * d:(g + 1) * d].reshape(p * bs, d)
    p, bs, kh, d = buf.shape
    if buf.dtype.itemsize != 2 or kh % 2:
        return lambda g: buf[:, :, g, :].reshape(p * bs, d)
    return _rows_reader(buf.reshape(p * bs * kh, d), kh)


def _ragged_kernel(cu_ref, ctx_ref, ns_ref, bt_ref,   # scalar prefetch
                   q_ref, kc_ref, vc_ref, o_ref,
                   kbuf, vbuf, sem, m_scr, l_scr, acc_scr, *,
                   scale, tile, heads, kv_heads, block_size, pages, align,
                   small, window=None):
    d = q_ref.shape[-1]
    rep = heads // kv_heads
    width = pages * block_size            # cached tokens per page group
    s_slots = ctx_ref.shape[0]
    mb = bt_ref.shape[0] // s_slots
    t_lo = pl.program_id(0) * tile        # this tile's stream rows
    t_hi = t_lo + tile
    ns = ns_ref[0]
    f32, i32 = jnp.float32, jnp.int32

    # rows no slot owns (stream padding) keep these zeros
    o_ref[...] = jnp.zeros_like(o_ref)

    def span(s):
        """Slot ``s`` in this tile: its stream rows [r0, r1), whether it
        has any, how many pages they may attend to (to the causal bound of
        the last row; 0 if the slot has no row here) and the first of
        them (0 without a window; under one, the page of the first row's
        oldest visible key: the pages before it may be gone)."""
        c = jnp.minimum(s, s_slots - 1)
        lo = cu_ref[c]
        nq = cu_ref[c + 1] - lo
        r0 = jnp.maximum(lo, t_lo)
        r1 = jnp.minimum(lo + nq, t_hi)
        live = (s < ns) & (r1 > r0)
        hi = ctx_ref[c] - nq + (r1 - lo) - 1   # absolute pos of row r1-1
        n_pg = jnp.where(live, jnp.clip(hi // block_size + 1, 1, mb), 0)
        if window is None:
            return lo, nq, ctx_ref[c], r0, r1, live, n_pg, 0
        first = ctx_ref[c] - nq + (r0 - lo)    # absolute pos of row r0
        pg0 = jnp.where(
            live, jnp.maximum(first - window + 1, 0) // block_size, 0)
        return lo, nq, ctx_ref[c], r0, r1, live, n_pg - pg0, pg0

    def fetch(s, pg0, grp, b):
        """Start the copies of slot ``s``'s page group ``grp`` counted
        from page ``pg0`` into buffer ``b``: always ``pages`` of them, so
        that one wait serves the group. Past the slot's last live page the
        table's clamped entries name blocks of the pool, whose keys the
        causal bound masks."""
        base = s * mb + pg0 + grp * pages
        for p in range(pages):
            page = bt_ref[jnp.minimum(base + p, s * mb + mb - 1)]
            pltpu.make_async_copy(kc_ref.at[page], kbuf.at[b, p],
                                  sem.at[0, b]).start()
            pltpu.make_async_copy(vc_ref.at[page], vbuf.at[b, p],
                                  sem.at[1, b]).start()

    def wait(b):
        for c, (ref, buf) in enumerate(((kc_ref, kbuf), (vc_ref, vbuf))):
            pltpu.make_async_copy(ref.at[pl.ds(0, pages)], buf.at[b],
                                  sem.at[c, b]).wait()

    def heads_of(b):
        return _head_reader(kbuf.at[b], d), _head_reader(vbuf.at[b], d)

    def online(q, keep, kg, vg, m_prev, l_prev, acc):
        """One page group of the online softmax for the product rows of
        ``q``; a row with no key kept is left exactly as it was."""
        sc = mxu_dot(q, kg, (((1,), (1,)), ((), ())),
                     preferred_element_type=f32) * scale
        sc = jnp.where(keep, sc, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # where, not only the shift: while a row has met no key of its own
        # m is the mask value and every masked score would weigh 1
        p = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = mxu_dot(p.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                     preferred_element_type=f32)
        return m_new, l_new, acc * alpha + pv

    def slot_body(carry):
        s, b, fetched = carry
        lo, nq, ctx, r0, r1, live, n_pg, pg0 = span(s)
        n_grp = (n_pg + pages - 1) // pages
        nxt_live, _, nxt_pg0 = span(s + 1)[-3:]

        @pl.when(live & (fetched == 0))
        def _():
            fetch(s, pg0, 0, b)

        def walk(attend):
            def group_body(grp, b):
                # next in flight while this one is computed: the slot's
                # next group, or after its last the next slot's first
                last = grp + 1 == n_grp

                @pl.when(~last | nxt_live)
                def _():
                    fetch(jnp.where(last, s + 1, s),
                          jnp.where(last, nxt_pg0, pg0),
                          jnp.where(last, 0, grp + 1), 1 - b)

                wait(b)
                attend(grp, b)
                return 1 - b
            jax.lax.fori_loop(0, n_grp, group_body, b)

        def columns(grp, qpos):
            """Which of the group's keys a product row at absolute
            position ``qpos`` (n, 1) sees."""
            col = pg0 * block_size + grp * width + jax.lax.broadcasted_iota(
                i32, (1, width), 1)
            seen = col <= qpos
            if window is not None:
                seen = seen & (col > qpos - window)
            return seen

        def product_rows(row0, n):
            """Of the tile's product rows [row0, row0 + n) (stream row x
            query head): which are the slot's, the slot's row each is
            counted from its first, and its query head."""
            rel = (row0 - (lo - t_lo) * heads
                   + jax.lax.broadcasted_iota(i32, (n, 1), 0))
            own = (rel >= 0) & (rel < nq * heads)
            row = jnp.floor((rel.astype(f32) + 0.5) * (1.0 / heads)).astype(
                i32)
            return own, row, rel - row * heads

        def stream(row0, n):
            return pl.ds(pl.multiple_of(row0, align), n)

        def init(row0, n):
            rows = stream(row0, n)
            m_scr[rows, :] = jnp.full((n, 128), _NEG_INF, f32)
            l_scr[rows, :] = jnp.zeros((n, 128), f32)
            acc_scr[rows, :] = jnp.zeros((n, d), f32)

        def store(row0, n):
            rows = stream(row0, n)
            own = product_rows(row0, n)[0]
            l = l_scr[rows, :1]
            val = (acc_scr[rows, :]
                   / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            # rows of other slots keep what their own sweep stored
            o_ref[rows, :] = jnp.where(own, val, o_ref[rows, :])

        def contiguous(row0, n):
            """The product rows [row0, row0 + n) in the stream's own order,
            one product a K/V head: of more than one K/V head a product
            row keeps only its own head's keys."""
            own, row, head = product_rows(row0, n)
            qpos = ctx - nq + row
            rows = stream(row0, n)

            def attend(grp, b):
                seen = own & columns(grp, qpos)
                q = q_ref[rows, :]
                k_head, v_head = heads_of(b)
                for g in range(kv_heads):
                    keep = seen
                    if kv_heads > 1:
                        keep = keep & (head >= g * rep) & (
                            head < (g + 1) * rep)
                    m, l, acc = online(q, keep, k_head(g), v_head(g),
                                       m_scr[rows, :1], l_scr[rows, :1],
                                       acc_scr[rows, :])
                    m_scr[rows, :] = jnp.broadcast_to(m, (n, 128))
                    l_scr[rows, :] = jnp.broadcast_to(l, (n, 128))
                    acc_scr[rows, :] = acc

            init(row0, n)
            walk(attend)
            store(row0, n)

        def by_head():
            """The whole tile, one product a K/V head of its ``rep`` query
            heads' rows (``rep`` blocks of ``tile`` rows, each a strided
            read of the stream)."""
            local = t_lo - lo + jax.lax.broadcasted_iota(i32, (tile, 1), 0)
            own = jnp.concatenate([(local >= 0) & (local < nq)] * rep,
                                  axis=0)
            qpos = jnp.concatenate([ctx - nq + local] * rep, axis=0)
            read_q = _rows_reader(q_ref, heads)

            def attend(grp, b):
                keep = own & columns(grp, qpos)
                k_head, v_head = heads_of(b)
                for g in range(kv_heads):
                    hs = range(g * rep, (g + 1) * rep)
                    rows = [pl.ds(h, tile, stride=heads) for h in hs]

                    def stacked(ref):
                        return jnp.concatenate([ref[r, :] for r in rows],
                                               axis=0)
                    m, l, acc = online(
                        jnp.concatenate([read_q(h) for h in hs], axis=0),
                        keep, k_head(g), v_head(g), stacked(m_scr)[:, :1],
                        stacked(l_scr)[:, :1], stacked(acc_scr))
                    for i, r in enumerate(rows):
                        part = slice(i * tile, (i + 1) * tile)
                        m_scr[r, :] = jnp.broadcast_to(m[part], (tile, 128))
                        l_scr[r, :] = jnp.broadcast_to(l[part], (tile, 128))
                        acc_scr[r, :] = acc[part]

            init(0, tile * heads)
            walk(attend)
            store(0, tile * heads)

        # the slot's product rows in the tile: one aligned window of
        # ``small`` rows when they fit in one (a decode row, a chunk's
        # tail), else the whole tile; of one K/V head either is one
        # product, of more the whole tile goes by K/V head
        first = (r0 - t_lo) * heads
        w0 = jnp.minimum(first // align * align, tile * heads - small)
        fits = (r1 - t_lo) * heads - w0 <= small
        pl.when(live & fits)(lambda: contiguous(w0, small))
        if kv_heads == 1:
            pl.when(live & ~fits)(lambda: contiguous(0, tile * heads))
        else:
            pl.when(live & ~fits)(by_head)
        return s + 1, (b + n_grp) % 2, (live & nxt_live).astype(i32)

    # slots are contiguous in the stream, so a tile holds a contiguous
    # slot range: find its first, walk until one starts past the tile
    s0 = jax.lax.while_loop(
        lambda s: (s + 1 < ns) & (cu_ref[s + 1] <= t_lo),
        lambda s: s + 1, jnp.int32(0))
    jax.lax.while_loop(
        lambda c: (c[0] < ns) & (cu_ref[c[0]] < t_hi), slot_body,
        (s0, jnp.int32(0), jnp.int32(0)))


# jitted on its own so that the layers of a model, which call it with one
# set of shapes, share one trace and one lowering of the kernel body: the
# body is the slow part of tracing a serving step (PERF.md, PR 28)
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _ragged_attend_pallas(q, kc, vc, bt, cu, ctx, num_seqs, scale,
                          interpret, window=None):
    t_total, h, d = q.shape
    folded = kc.ndim == 3
    if folded:
        _, bs, lanes = kc.shape
        kh, page = lanes // d, (bs, lanes)
    else:
        _, bs, kh, _ = kc.shape
        page = (bs, kh, d)
    _, mb = bt.shape
    if not interpret and not folded and kh * kc.dtype.itemsize < 4:
        # Mosaic pads a ref's second-minor dim to one 32-bit sublane
        # word and then refuses every slice of it ("must be aligned to
        # tiling"), so no page of such a cache can be copied
        raise NotImplementedError(
            f"the compiled ragged kernel cannot fetch pages of a "
            f"{kc.dtype} cache with {kh} KV head(s) per shard: keep "
            f"kv_heads * itemsize >= 4 (fewer head shards)")
    rep = h // kh
    tile = _tile_rows(t_total, rep)
    n_qb = -(-t_total // tile)
    t_pad = n_qb * tile
    pages = max(1, min(mb, _group_tokens(window) // bs))
    # product rows: a tile's start at a whole sublane tile of q, and the
    # window that holds one stream row's heads wherever it starts
    align = max(8, 32 // q.dtype.itemsize)
    small = -(-(h + align - math.gcd(h, align)) // align) * align
    # q and out as the stream (T * H, D): a row's heads side by side on
    # the row axis (free reshapes)
    q2 = jnp.pad(q, ((0, t_pad - t_total), (0, 0), (0, 0))).reshape(
        t_pad * h, d)

    def tile_of(qb, *_):
        return (qb, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, tile=tile, heads=h, kv_heads=kh,
        block_size=bs, pages=pages, align=align, small=small,
        **({} if window is None else {"window": window}))
    # what the call holds in VMEM: K and V groups (two buffers each), q and
    # out tiles (double-buffered), the state, and a group's scores,
    # probabilities and mask at the widest product, with room to spare
    width = pages * bs
    need = (4 * pages * math.prod(page) * kc.dtype.itemsize
            + 4 * tile * h * d * q.dtype.itemsize
            + tile * h * (256 + d) * 4
            + 6 * tile * rep * width * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((tile * h, d), tile_of, memory_space=_VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile * h, d), tile_of, memory_space=_VMEM),
        scratch_shapes=[
            _VMEM((2, pages) + page, kc.dtype),
            _VMEM((2, pages) + page, vc.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            _VMEM((tile * h, 128), jnp.float32),
            _VMEM((tile * h, 128), jnp.float32),
            _VMEM((tile * h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad * h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + 16 * 2 ** 20),
        interpret=interpret,
        name="ragged_paged_attention",
    )(cu.astype(jnp.int32), ctx.astype(jnp.int32),
      jnp.reshape(num_seqs.astype(jnp.int32), (1,)),
      jnp.maximum(bt, 0).reshape(-1).astype(jnp.int32), q2, kc, vc)
    return out.reshape(t_pad, h, d)[:t_total]


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------
def _resolve_impl(impl):
    if impl is None:
        impl = os.environ.get("PADDLE_RAGGED_ATTN_IMPL") or (
            "pallas" if jax.default_backend() == "tpu" else "ref")
    if impl not in ("ref", "pallas", "interpret"):
        raise ValueError(f"unknown ragged attention impl: {impl!r}")
    return impl


def ragged_paged_attention(q, k_new, v_new, key_cache, value_cache,
                           block_tables, cu_seqlens, context_lens,
                           num_seqs, *, scale=None, impl=None, window=None,
                           v_lanes=None):
    """See module docstring for the contract. Returns (out, kc', vc').
    ``v_lanes`` n with ``value_cache`` None is the latent call: one
    cache whose entry is the key and, in its first n lanes, the value
    (``sparse_latent_attention.py: latent_attention``).
    ``window`` w (None = full): a query at position p attends keys
    p-w+1..p, and the page walk starts at the page of the first row's
    oldest visible key, so the cost does not grow with the context and
    block-table entries behind the window may be gone (-1); the K/V call
    and the latent call take it alike.
    ``k_new``/``v_new`` None is the read-only call: nothing is written
    and the caches come back as they were (a layer that attends another
    layer's pages)."""
    q = jnp.asarray(q)
    if v_lanes is not None:
        if value_cache is not None or v_new is not None:
            raise ValueError("the latent call takes one cache and one new "
                             "entry a row (value_cache and v_new None)")
        # imported here: that module imports this one's stream layout,
        # scatter and reference, and a K/V call never needs it
        from paddle_tpu.ops.pallas.sparse_latent_attention import (
            latent_attention,
        )
        out, cache = latent_attention(
            q, k_new, key_cache, block_tables, cu_seqlens, context_lens,
            num_seqs, v_lanes=int(v_lanes), window=window, scale=scale,
            impl=impl)
        return out, cache, None
    read_only = k_new is None
    if read_only:
        k_new = v_new = jnp.zeros((0,), q.dtype)    # placeholders, unread
    k_new = jnp.asarray(k_new)
    v_new = jnp.asarray(v_new)
    key_cache = jnp.asarray(key_cache)
    value_cache = jnp.asarray(value_cache)
    t_total, h, d = q.shape
    s_slots, _ = block_tables.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    impl = _resolve_impl(impl)
    cu = jnp.asarray(cu_seqlens).astype(jnp.int32)
    ctx = jnp.asarray(context_lens).astype(jnp.int32)
    bt = jnp.asarray(block_tables).astype(jnp.int32)
    ns = jnp.asarray(num_seqs).astype(jnp.int32)
    # a full-attention call passes no window at all: its trace, and the
    # jit cache entry of the kernel body, are the ones it had before
    win = {} if window is None else {"window": int(window)}

    def local(q, k_new, v_new, key_cache, value_cache, bt, cu, ctx, ns):
        seg, pos, valid = _token_layout(t_total, s_slots, cu, ctx, ns)
        if read_only:
            kc, vc = key_cache, value_cache
        else:
            with jax.named_scope("kv_update"):      # the cache scatter
                kc = _write_kv(key_cache, k_new, bt, seg, pos)
                vc = _write_kv(value_cache, v_new, bt, seg, pos)
        with jax.named_scope("attention"):
            if impl == "ref":
                out = _ragged_attend_ref(q, kc, vc, bt, ctx, seg, pos,
                                         valid, scale, **win)
            else:
                out = _ragged_attend_pallas(
                    q, kc, vc, bt, cu, ctx, ns, scale,
                    interpret=(impl == "interpret"), **win)
        return out, kc, vc

    decl = declared()
    if decl is not None and decl[1] is not None and key_cache.ndim == 3:
        raise NotImplementedError(
            "a folded (blocks, block_size, KH*D) cache has no head axis "
            "to shard over a mesh")
    if decl is not None and decl[1] is not None:
        # heads are sharded over a mesh axis (TP serving): every head is
        # independent, so each shard runs the same program on its own
        # heads and its own slice of the cache's kv-head dim
        mesh, ax, _ = decl
        hs = PartitionSpec(None, ax, None)
        cs = PartitionSpec(None, None, ax, None)
        r = PartitionSpec()
        new = r if read_only else hs
        local = jax.shard_map(
            local, mesh=mesh, in_specs=(hs, new, new, cs, cs, r, r, r, r),
            out_specs=(hs, cs, cs), check_vma=False)
    return local(q, k_new, v_new, key_cache, value_cache, bt, cu, ctx, ns)
