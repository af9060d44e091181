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
  live (q tile, slot, page) triples, not the batch's capacity. The grid
  is the q tiles of the packed stream alone: q and out move through
  (block_q, H*D) BlockSpec tiles, the caches stay in HBM. Inside a tile
  the kernel walks, from the scalar-prefetched cu_seqlens/context_lens/
  block_tables, the contiguous range of slots that have rows in it and,
  per slot, its pages up to the causal bound of its last row there, in
  groups of ``pages`` pages (128 tokens): each group is ``pages`` async
  copies into a double-buffered VMEM scratch, in the cache's own
  (BS, KH, D) layout, the next group (or the next slot's first) in
  flight while the present one is computed. Per group and KV head one
  matmul of the head's ``rep`` query heads, stacked on the row axis,
  against the group's keys, online-softmax state in VMEM scratch. A slot
  whose rows fit one aligned slab of 8/16 rows (a decode row, a chunk's
  tail) computes on that slab only; any other on the whole tile, the
  other slots' rows masked. Tiles past the last token do nothing but
  zero their output. Compiled, it needs head_dim % 128 == 0 and
  kv_heads * itemsize >= 4 per shard.

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


def _pick_block_q(t):
    for b in (128, 64, 32, 16, 8):
        if b <= t:
            return b
    return t


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
def _head_reader(buf, d=None):
    """``read(g)``: KV head ``g`` of a fetched page group ``buf`` as a
    (P*BS, D) matrix. A folded group (P, BS, KH*D) keeps its heads side
    by side on lanes: a head is a static ``d``-wide lane slice. Else
    ``buf`` is (P, BS, KH, D). The pages keep the cache's own
    layout (folding heads onto lanes would re-tile the whole cache every
    call), so a head is a static index on the sublane axis. Rows of 16
    bits share a 32-bit sublane word with the next head's: there the
    head is one half of every KH/2-th word of the (P*BS*KH/2, D) view,
    one strided load instead of a row-by-row gather."""
    if len(buf.shape) == 3:
        p, bs, _ = buf.shape
        return lambda g: buf[:, :, g * d:(g + 1) * d].reshape(p * bs, d)
    p, bs, kh, d = buf.shape
    n = p * bs
    if buf.dtype.itemsize != 2 or kh % 2:
        return lambda g: buf[:, :, g, :].reshape(n, d)
    words = buf.reshape(n * kh, d).bitcast(jnp.uint32)

    def read(g):
        w = words[pl.ds(g // 2, n, stride=kh // 2), :]
        w = w & jnp.uint32(0xFFFF0000) if g % 2 else w << 16
        return pltpu.bitcast(w, jnp.float32).astype(buf.dtype)
    return read


def _ragged_kernel(cu_ref, ctx_ref, ns_ref, bt_ref,   # scalar prefetch
                   q_ref, kc_ref, vc_ref, o_ref,
                   kbuf, vbuf, sem, m_scr, l_scr, acc_scr, *,
                   scale, block_q, slab, block_size, pages, n_heads,
                   kv_heads, head_dim, window=None):
    d = head_dim
    rep = n_heads // kv_heads
    width = pages * block_size            # KV tokens per page group
    s_slots = ctx_ref.shape[0]
    mb = bt_ref.shape[0] // s_slots
    t_lo = pl.program_id(0) * block_q     # this q tile's stream rows
    t_hi = t_lo + block_q
    ns = ns_ref[0]

    # rows no slot owns (stream padding) keep these zeros; a page group's
    # unfetched tail is multiplied by exact-zero probabilities, so what
    # the V buffer starts with must be finite
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)

    def span(s):
        """Slot ``s`` in this tile: its stream rows [r0, r1), the first
        KV page they may attend to (0 without a window; under one, the
        page of the first row's oldest visible key: the pages before it
        may be gone) and how many pages from there on (up to the causal
        bound of the last row; 0 if the slot has no row here)."""
        c = jnp.minimum(s, s_slots - 1)
        lo = cu_ref[c]
        nq = cu_ref[c + 1] - lo
        r0 = jnp.maximum(lo, t_lo)
        r1 = jnp.minimum(lo + nq, t_hi)
        live = (s < ns) & (r1 > r0)
        hi = ctx_ref[c] - nq + (r1 - lo) - 1   # absolute pos of row r1-1
        n_pg = jnp.where(live, jnp.clip(hi // block_size + 1, 1, mb), 0)
        if window is None:
            return lo, nq, ctx_ref[c], r0, r1, 0, live, n_pg
        first = ctx_ref[c] - nq + (r0 - lo)    # absolute pos of row r0
        pg0 = jnp.where(
            live, jnp.maximum(first - window + 1, 0) // block_size, 0)
        return lo, nq, ctx_ref[c], r0, r1, pg0, live, n_pg - pg0

    def copies(p, b, page):
        return (pltpu.make_async_copy(kc_ref.at[page], kbuf.at[b, p],
                                      sem.at[0, b]),
                pltpu.make_async_copy(vc_ref.at[page], vbuf.at[b, p],
                                      sem.at[1, b]))

    def fetch(s, pg0, grp, n_pg, b):
        """Start the copies of slot ``s``'s page group ``grp`` counted
        from page ``pg0`` (the first ``pages`` of its remaining ``n_pg``
        pages) into buffer ``b``."""
        def one(p, _):
            entry = s * mb + grp * pages + p
            if window is not None:
                entry = entry + pg0
            for c in copies(p, b, bt_ref[entry]):
                c.start()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(n_pg, pages), one, 0)

    def wait(n_pg, b):
        def one(p, _):
            for c in copies(p, b, 0):
                c.wait()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(n_pg, pages), one, 0)

    def slot_body(carry):
        s, b, fetched = carry
        lo, nq, ctx, r0, r1, pg0, live, n_pg = span(s)
        n_grp = (n_pg + pages - 1) // pages
        nxt_pg0, nxt_live, nxt_pg = span(s + 1)[-3:]

        @pl.when(live & (fetched == 0))
        def _():
            fetch(s, pg0, 0, n_pg, b)

        # the slot's rows in the tile: one aligned slab of ``slab`` rows
        # when they fit in one (a decode row, a chunk's tail), else the
        # whole tile with the other slots' rows masked
        first = (r0 - t_lo) // slab * slab
        small = r1 - t_lo <= first + slab

        def on_rows(fn):
            if slab < block_q:
                pl.when(live & small)(
                    lambda: fn(pl.multiple_of(first, slab), slab))
            pl.when(live & ~small if slab < block_q else live)(
                lambda: fn(0, block_q))

        def init(row0, n):
            rows = pl.ds(row0, n)
            m_scr[:, rows, :] = jnp.full((n_heads, n, 128), _NEG_INF,
                                         jnp.float32)
            l_scr[:, rows, :] = jnp.zeros((n_heads, n, 128), jnp.float32)
            acc_scr[rows, :] = jnp.zeros((n, n_heads * d), jnp.float32)

        def attend(grp, b, row0, n):
            rows = pl.ds(row0, n)
            row = row0 + jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)
            col = grp * width + jax.lax.broadcasted_iota(
                jnp.int32, (n, width), 1)
            if window is not None:
                col = col + pg0 * block_size
            local = t_lo + row - lo                      # seq-local q index
            qpos = ctx - nq + local                      # absolute position
            mask = (local >= 0) & (local < nq) & (col <= qpos)
            if window is not None:
                mask = mask & (col > qpos - window)
            mask = jnp.concatenate([mask] * rep, axis=0)
            if len(kbuf.shape) == 4:                     # folded pages
                k_head, v_head = (_head_reader(kbuf.at[b], d),
                                  _head_reader(vbuf.at[b], d))
            else:
                k_head, v_head = _head_reader(kbuf.at[b]), _head_reader(
                    vbuf.at[b])
            for g in range(kv_heads):
                # q/out heads live on the lane axis (the (T, H*D) view),
                # so a head is a static 128-aligned lane slice, and the
                # ``rep`` query heads of a KV head stack on the row axis:
                # one matmul per KV head over the whole page group
                heads = range(g * rep, (g + 1) * rep)
                qg = jnp.concatenate(
                    [q_ref[rows, h * d:(h + 1) * d] for h in heads], axis=0)
                hs = [slice(h * d, (h + 1) * d) for h in heads]
                kg = k_head(g)
                vg = v_head(g)
                sc = mxu_dot(
                    qg, kg, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(mask, sc, _NEG_INF)
                m_prev = jnp.concatenate(
                    [m_scr[g * rep + r, rows, :1] for r in range(rep)],
                    axis=0)
                l_prev = jnp.concatenate(
                    [l_scr[g * rep + r, rows, :1] for r in range(rep)],
                    axis=0)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(sc - m_new)
                l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
                pv = mxu_dot(
                    p.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                for r, h in enumerate(hs):
                    part = slice(r * n, (r + 1) * n)
                    acc_scr[rows, h] = (acc_scr[rows, h] * alpha[part]
                                        + pv[part])
                    m_scr[g * rep + r, rows, :] = jnp.broadcast_to(
                        m_new[part], (n, 128))
                    l_scr[g * rep + r, rows, :] = jnp.broadcast_to(
                        l_new[part], (n, 128))

        def store(row0, n):
            rows = pl.ds(row0, n)
            local = (t_lo + row0 - lo
                     + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0))
            ok = (local >= 0) & (local < nq)
            for h in range(n_heads):
                hd = slice(h * d, (h + 1) * d)
                l = l_scr[h, rows, :1]
                val = (acc_scr[rows, hd]
                       / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
                # rows of other slots keep what their own sweep stored
                o_ref[rows, hd] = jnp.where(ok, val, o_ref[rows, hd])

        on_rows(init)

        def group_body(grp, b):
            # next in flight while this one is computed: the slot's next
            # group, or after its last the next slot's first
            last = grp + 1 == n_grp

            @pl.when(~last | nxt_live)
            def _():
                fetch(jnp.where(last, s + 1, s),
                      jnp.where(last, nxt_pg0, pg0) if window else 0,
                      jnp.where(last, 0, grp + 1),
                      jnp.where(last, nxt_pg, n_pg - (grp + 1) * pages),
                      1 - b)

            wait(n_pg - grp * pages, b)
            on_rows(functools.partial(attend, grp, b))
            return 1 - b

        b = jax.lax.fori_loop(0, n_grp, group_body, b)
        on_rows(store)
        return s + 1, b, (live & nxt_live).astype(jnp.int32)

    # slots are contiguous in the stream, so a tile holds a contiguous
    # slot range: find its first, walk until one starts past the tile
    s0 = jax.lax.while_loop(
        lambda s: (s + 1 < ns) & (cu_ref[s + 1] <= t_lo),
        lambda s: s + 1, jnp.int32(0))
    jax.lax.while_loop(
        lambda c: (c[0] < ns) & (cu_ref[c[0]] < t_hi), slot_body,
        (s0, jnp.int32(0), jnp.int32(0)))


# page groups of this many KV tokens: one lane width of scores
_GROUP_TOKENS = 128


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
    block_q = _pick_block_q(t_total)
    n_qb = -(-t_total // block_q)
    t_pad = n_qb * block_q
    pages = max(1, min(mb, _GROUP_TOKENS // bs))
    ns = jnp.reshape(num_seqs.astype(jnp.int32), (1,))
    bt_flat = jnp.maximum(bt, 0).reshape(-1).astype(jnp.int32)
    q2 = q.reshape(t_total, h * d)
    if t_pad != t_total:
        q2 = jnp.pad(q2, ((0, t_pad - t_total), (0, 0)))

    def q_map(qb, cu_r, ctx_r, ns_r, bt_r):
        return (qb, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, block_q=block_q,
        slab=max(8, 32 // q.dtype.itemsize), block_size=bs, pages=pages,
        n_heads=h, kv_heads=kh, head_dim=d,
        **({} if window is None else {"window": window}))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((block_q, h * d), q_map, memory_space=_VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_q, h * d), q_map,
                               memory_space=_VMEM),
        scratch_shapes=[
            _VMEM((2, pages) + page, kc.dtype),
            _VMEM((2, pages) + page, vc.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            _VMEM((h, block_q, 128), jnp.float32),
            _VMEM((h, block_q, 128), jnp.float32),
            _VMEM((block_q, h * d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, h * d), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(cu.astype(jnp.int32), ctx.astype(jnp.int32), ns, bt_flat, q2, kc, vc)
    return out[:t_total].reshape(t_total, h, d)


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
