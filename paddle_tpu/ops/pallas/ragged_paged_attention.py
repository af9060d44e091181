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
* ``key_cache/value_cache``: (num_blocks, block_size, KH, D) paged cache.
* ``block_tables``: (S, MB) int32 physical block ids per slot (-1 pads).
* ``cu_seqlens``:   (S+1,) int32 exclusive prefix sum of per-slot new-token
                    counts (cu_seqlens[0] == 0).
* ``context_lens``: (S,) int32 total tokens in cache per slot AFTER this
                    step's new tokens are written (prefix + new).
* ``num_seqs``:     int32 scalar — live slots; trailing slots are padding.

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
* ``_ragged_attend_pallas`` — Pallas TPU kernel, grid (q_tiles, S,
  kv_pages) with scalar-prefetched cu_seqlens/context_lens/block_tables.
  q and out move through (block_q, H*D) BlockSpec tiles of the packed
  stream (a tile may hold rows of several slots; each slot's rows are
  attended and stored on that slot's sweep), one (BS, KH, D) KV page per
  grid step, online-softmax accumulators in VMEM scratch. Steps whose
  slot has no row in the tile, or whose page lies past the causal bound,
  do nothing and fetch nothing. Compiled, it needs head_dim % 128 == 0.

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
    cache_flat = cache_flat.at[fi].set(new.astype(cache.dtype),
                                       mode="drop")
    return cache_flat.reshape(cache.shape)


# ---------------------------------------------------------------------------
# reference implementation (semantics oracle; the non-TPU path)
# ---------------------------------------------------------------------------
def _ragged_attend_ref(q, kc, vc, bt, ctx, seg, pos, valid, scale):
    t_total, h, d = q.shape
    nb, bs, kh, _ = kc.shape
    mb = bt.shape[1]
    bt_tok = bt[seg]                                     # (T, MB)
    safe = jnp.maximum(bt_tok, 0)
    k_seq = kc[safe].reshape(t_total, mb * bs, kh, d)
    v_seq = vc[safe].reshape(t_total, mb * bs, kh, d)
    if kh != h:
        rep = h // kh
        k_seq = jnp.repeat(k_seq, rep, axis=2)
        v_seq = jnp.repeat(v_seq, rep, axis=2)
    logits = jnp.einsum("thd,tlhd->thl", q, k_seq) * scale
    lpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]
    att = ((lpos <= pos[:, None])
           & (bt_tok >= 0).repeat(bs, axis=1)
           & valid[:, None])                             # (T, L)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, logits.dtype)
    logits = jnp.where(att[:, None, :], logits, neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("thl,tlhd->thd", probs.astype(v_seq.dtype), v_seq)
    # where, not multiply: padded q rows may be NaN and NaN * 0 == NaN
    return jnp.where(valid[:, None, None], out, 0)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _q_block_span(cu_ref, ctx_ref, ns_ref, i, qb, block_q, block_size):
    """For q tile ``qb`` (stream rows [qb*block_q, (qb+1)*block_q)) and
    sequence slot ``i``: whether any of the slot's rows fall in the tile,
    and the last KV page the tile's rows of that slot may attend to
    (causal upper bound). Scalar math on the prefetched refs only, so the
    index maps can call it too."""
    lo = cu_ref[i]
    nq = cu_ref[i + 1] - lo
    # last stream row of slot i inside the tile (exclusive)
    end = jnp.minimum(lo + nq, (qb + 1) * block_q)
    live = (i < ns_ref[0]) & (end > jnp.maximum(lo, qb * block_q))
    hi = ctx_ref[i] - nq + (end - lo) - 1      # absolute pos of that row
    last_j = jnp.where(live, jnp.maximum(hi, 0) // block_size, 0)
    return live, last_j


def _ragged_kernel(cu_ref, ctx_ref, ns_ref, bt_ref,   # scalar prefetch
                   q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale, block_q, block_size, n_heads, kv_heads, head_dim):
    qb = pl.program_id(0)         # q tile of the packed token stream
    i = pl.program_id(1)          # sequence slot
    j = pl.program_id(2)          # kv page (position within block table)
    d = head_dim
    rep = n_heads // kv_heads

    # the out tile stays resident across the (i, j) sweep of one q tile;
    # rows no slot owns (stream padding) keep these zeros
    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    live, last_j = _q_block_span(cu_ref, ctx_ref, ns_ref, i, qb, block_q,
                                 block_size)

    @pl.when(live & (j == 0))
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = live & (j <= last_j)
    lo = cu_ref[i]
    nq = cu_ref[i + 1] - lo
    ctx = ctx_ref[i]

    @pl.when(run)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_size), 0)
        col = (j * block_size
               + jax.lax.broadcasted_iota(jnp.int32,
                                          (block_q, block_size), 1))
        local = qb * block_q + row - lo                  # seq-local q index
        qpos = ctx - nq + local                          # absolute position
        mask = (local >= 0) & (local < nq) & (col <= qpos)
        for h in range(n_heads):
            # q/out heads live on the lane axis (the (T, H*D) view), so
            # a head is a static 128-aligned lane slice; the KV page keeps
            # the cache's own (BS, KH, D) layout (folding its heads onto
            # lanes would re-tile the whole cache every call) and a head
            # is a static index on its sublane axis
            qh = q_ref[:, h * d:(h + 1) * d]
            g = h // rep
            kh_blk = k_ref[0, :, g, :]
            s = mxu_dot(
                qh, kh_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[h, :, :1]
            l_prev = l_scr[h, :, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            vh_blk = v_ref[0, :, g, :]
            acc_scr[:, h * d:(h + 1) * d] = (
                acc_scr[:, h * d:(h + 1) * d] * alpha
                + mxu_dot(
                    p.astype(vh_blk.dtype), vh_blk,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(run & (j == last_j))
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        local = qb * block_q + row - lo
        ok = (local >= 0) & (local < nq)
        for h in range(n_heads):
            l = l_scr[h, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            val = (acc_scr[:, h * d:(h + 1) * d] / l_safe).astype(
                o_ref.dtype)
            # rows of this tile owned by OTHER slots keep what their own
            # (i, last_j) step stored
            cur = o_ref[:, h * d:(h + 1) * d]
            o_ref[:, h * d:(h + 1) * d] = jnp.where(ok, val, cur)


def _ragged_attend_pallas(q, kc, vc, bt, cu, ctx, num_seqs, scale,
                          interpret):
    t_total, h, d = q.shape
    _, bs, kh, _ = kc.shape
    s_slots, mb = bt.shape
    block_q = _pick_block_q(t_total)
    n_qb = -(-t_total // block_q)
    t_pad = n_qb * block_q
    ns = jnp.reshape(num_seqs.astype(jnp.int32), (1,))
    bt_flat = jnp.maximum(bt, 0).reshape(-1).astype(jnp.int32)
    q2 = q.reshape(t_total, h * d)
    if t_pad != t_total:
        q2 = jnp.pad(q2, ((0, t_pad - t_total), (0, 0)))

    def q_map(qb, i, j, cu_r, ctx_r, ns_r, bt_r):
        return (qb, 0)

    def kv_map(qb, i, j, cu_r, ctx_r, ns_r, bt_r):
        # pages past the causal bound (and every page of a slot with no
        # row in this q tile) re-name the last needed page: an unchanged
        # block index is not fetched again
        _, last_j = _q_block_span(cu_r, ctx_r, ns_r, i, qb, block_q, bs)
        return (bt_r[i * mb + jnp.minimum(j, last_j)], 0, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, block_q=block_q, block_size=bs,
        n_heads=h, kv_heads=kh, head_dim=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_qb, s_slots, mb),
        in_specs=[
            pl.BlockSpec((block_q, h * d), q_map, memory_space=_VMEM),
            pl.BlockSpec((1, bs, kh, d), kv_map, memory_space=_VMEM),
            pl.BlockSpec((1, bs, kh, d), kv_map, memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((block_q, h * d), q_map,
                               memory_space=_VMEM),
        scratch_shapes=[
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
def ragged_paged_attention(q, k_new, v_new, key_cache, value_cache,
                           block_tables, cu_seqlens, context_lens,
                           num_seqs, *, scale=None, impl=None):
    """See module docstring for the contract. Returns (out, kc', vc')."""
    q = jnp.asarray(q)
    k_new = jnp.asarray(k_new)
    v_new = jnp.asarray(v_new)
    key_cache = jnp.asarray(key_cache)
    value_cache = jnp.asarray(value_cache)
    t_total, h, d = q.shape
    s_slots, _ = block_tables.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if impl is None:
        impl = os.environ.get("PADDLE_RAGGED_ATTN_IMPL") or (
            "pallas" if jax.default_backend() == "tpu" else "ref")
    if impl not in ("ref", "pallas", "interpret"):
        raise ValueError(f"unknown ragged attention impl: {impl!r}")
    cu = jnp.asarray(cu_seqlens).astype(jnp.int32)
    ctx = jnp.asarray(context_lens).astype(jnp.int32)
    bt = jnp.asarray(block_tables).astype(jnp.int32)
    ns = jnp.asarray(num_seqs).astype(jnp.int32)

    def local(q, k_new, v_new, key_cache, value_cache, bt, cu, ctx, ns):
        seg, pos, valid = _token_layout(t_total, s_slots, cu, ctx, ns)
        with jax.named_scope("kv_update"):      # the cache scatter
            kc = _write_kv(key_cache, k_new, bt, seg, pos)
            vc = _write_kv(value_cache, v_new, bt, seg, pos)
        with jax.named_scope("attention"):
            if impl == "ref":
                out = _ragged_attend_ref(q, kc, vc, bt, ctx, seg, pos,
                                         valid, scale)
            else:
                out = _ragged_attend_pallas(
                    q, kc, vc, bt, cu, ctx, ns, scale,
                    interpret=(impl == "interpret"))
        return out, kc, vc

    decl = declared()
    if decl is not None and decl[1] is not None:
        # heads are sharded over a mesh axis (TP serving): every head is
        # independent, so each shard runs the same program on its own
        # heads and its own slice of the cache's kv-head dim
        mesh, ax, _ = decl
        hs = PartitionSpec(None, ax, None)
        cs = PartitionSpec(None, None, ax, None)
        r = PartitionSpec()
        local = jax.shard_map(
            local, mesh=mesh, in_specs=(hs, hs, hs, cs, cs, r, r, r, r),
            out_specs=(hs, cs, cs), check_vma=False)
    return local(q, k_new, v_new, key_cache, value_cache, bt, cu, ctx, ns)
