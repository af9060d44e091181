"""The latent attention kernel: multi-head latent attention in its absorbed
form over ONE paged cache whose entry is every query head's key (all its
lanes) and, in its first ``v_lanes`` lanes, its value. One kernel body
serves the three latent calls, each under its own name in the device
trace:

* ``ragged_paged_attention``: every key a row causally sees
  (``models/mla_moe.py``; reached through
  ``ragged_paged_attention(..., v_lanes=)``);
* ``ragged_window_latent_attention``: the last ``window`` of them, over a
  window pool whose table entries behind the window may be gone (-1)
  (``models/dots3.py``'s sliding layers; ``..., v_lanes=, window=``);
* ``ragged_sparse_latent_attention``: those of them a learned indexer
  selected (``ops/sparse_index.select_topk``; ``models/dots3.py``'s full
  layers; :func:`sparse_latent_attention`).

The K/V calls have a kernel of their own
(``ragged_paged_attention.py: _ragged_kernel``); the two files share the
stream's contract (rows packed by slot, ``cu_seqlens``, ``context_lens``,
one block table a slot), the cache scatter and the ``jnp`` reference.

* ``q``:        (T, H, W)  queries in the absorbed form, one a head.
* ``k_new``:    (T, W)     the rows' own entries, written first (None:
                           nothing is written).
* ``cache``:    (num_blocks, block_size, W) latent pool.
* ``selected``: None, or (T, MB * block_size) mask by the slot's LOGICAL
                position, non-zero = the row attends to that key if it
                causally sees it (a position whose block-table entry is
                -1 must not be selected).

Returns ``(out (T, H, v_lanes), cache')``; a row that sees no key
(stream padding, a row whose selection holds none it sees) reads zeros.

The kernel: the stream as ``(T * H, W)``, a row's heads side by side on
the row axis (a free reshape: no re-tile of q and out, no head groups, no
restacking), grid over tiles of about ``_PRODUCT_ROWS`` (2,048) product
rows: 128 stream rows of 16 heads, 16 of 128 (``_tile_rows``). A tile
finds the slots that have rows in it and, per slot, walks its pages (from
page 0, or under a window the page of the first row's oldest visible key,
to the causal bound of its last row there) in double-buffered groups of
``_GROUP_TOKENS`` (512) tokens (under a window about half the window:
``_group_tokens``), the next group or the next slot's first in flight: one
product of the tile's ``rows x H`` queries with the group's keys, the mask
(causal bound, window, selection: one row of it for all of a stream row's
heads), online softmax in float32, one product with the keys' first
``v_lanes`` lanes. A slot with ONE row in the tile (every decode row)
computes on that row's H queries alone.

``impl`` as the ragged op's: ``"ref"`` (``_ragged_attend_ref``),
``"pallas"`` (compiled; W and ``v_lanes`` multiples of 128, H of 16),
``"interpret"``; None picks Pallas on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.common import declared, mxu_dot
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _ragged_attend_ref, _resolve_impl, _token_layout, _write_kv,
)

__all__ = ["latent_attention", "sparse_latent_attention"]

_VMEM = pltpu.VMEM
_NEG_INF = -1e30
# rows of a tile's products (stream rows x heads) and cached tokens a page
# group (their columns). Read on the chip, a call (PERF.md, PRs 36 and 38):
# 128 heads of 640 lanes, a chunk 11k deep: 1,024 x 256 14.9 ms, 1,024 x
# 512 12.9, 1,024 x 1,024 12.6, 2,048 x 512 11.9, 2,048 x 1,024 12.0; 16
# heads, a chunk 2k deep beside 30 decode rows: 2,048 x 512 0.634 ms,
# 1,024 x 512 0.653, 2,048 x 256 0.739, 2,048 x 1,024 0.703, 4,096 x 512
# 0.638
_PRODUCT_ROWS = 2048
_GROUP_TOKENS = 512
_VMEM_LIMIT = 64 * 1024 * 1024
# what a tile's q and out (both double-buffered), accumulator, its update
# and a page group's scores, probabilities and mask may take of that limit
_TILE_BYTES = 40 * 1024 * 1024


def _tile_rows(heads, lanes, v_lanes, itemsize, group):
    """Stream rows a q tile: ``_PRODUCT_ROWS`` product rows, halved until
    the tile's operands fit ``_TILE_BYTES`` (16 heads of 640 lanes: 128
    rows; 128 heads of 640: 16; 64 heads of 1,152: 16)."""
    a_row = (2 * itemsize * (lanes + v_lanes) + 8 * v_lanes + 12 * group
             + 1024)
    rows = _PRODUCT_ROWS
    while rows > heads and rows * a_row > _TILE_BYTES:
        rows //= 2
    return max(1, rows // heads)


def _group_tokens(window):
    """Cached tokens a page group. Under a window a tile of n rows sees
    ``window + n - 1`` keys wherever it stands: groups of half the window
    waste less of each product than 512 would."""
    if window is None:
        return _GROUP_TOKENS
    return max(128, min(_GROUP_TOKENS, 1 << ((window // 2).bit_length() - 1)))


def _stacked(per_row, heads):
    """(n, w) values a stream row -> (n * heads, w): each row's, once a
    head, in the order of the (T * H, W) stream."""
    n, w = per_row.shape
    return jnp.concatenate(
        [jnp.broadcast_to(per_row[i:i + 1], (heads, w)) for i in range(n)],
        axis=0)


def _latent_kernel(cu_ref, ctx_ref, ns_ref, bt_ref,    # scalar prefetch
                   q_ref, sel_ref, kc_ref, o_ref,
                   kbuf, sem, m_scr, l_scr, acc_scr=None, *,
                   scale, tile, heads, block_size, pages, v_lanes,
                   selected=True, window=None):
    if not selected:
        # every key a row causally sees (under ``window``, the last
        # ``window`` of them): the call passes one operand less, so its
        # refs arrive one place to the left
        sel_ref, kc_ref, o_ref, kbuf, sem, m_scr, l_scr, acc_scr = (
            None, sel_ref, kc_ref, o_ref, kbuf, sem, m_scr, l_scr)
    d = q_ref.shape[-1]
    width = pages * block_size            # cached tokens per page group
    s_slots = ctx_ref.shape[0]
    mb = bt_ref.shape[0] // s_slots
    t_lo = pl.program_id(0) * tile        # this tile's stream rows
    t_hi = t_lo + tile
    ns = ns_ref[0]

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

    def copy(p, b, page):
        return pltpu.make_async_copy(kc_ref.at[page], kbuf.at[b, p],
                                     sem.at[b])

    def fetch(s, pg0, grp, b):
        """Start the copies of slot ``s``'s page group ``grp`` counted
        from page ``pg0`` into buffer ``b``: always ``pages`` of them, so
        that one wait serves the group (a wait a page cost a decode row a
        quarter of its time: PERF.md, PR 38). Past the slot's last live
        page the table's clamped entries name blocks of the pool, whose
        keys the causal bound masks."""
        base = s * mb + pg0 + grp * pages
        for p in range(pages):
            copy(p, b, bt_ref[jnp.minimum(base + p, s * mb + mb - 1)]).start()

    def wait(b):
        pltpu.make_async_copy(kc_ref.at[pl.ds(0, pages)], kbuf.at[b],
                              sem.at[b]).wait()

    def slot_body(carry):
        s, b, fetched = carry
        lo, nq, ctx, r0, r1, live, n_pg, pg0 = span(s)
        n_grp = (n_pg + pages - 1) // pages
        nxt_live, _, nxt_pg0 = span(s + 1)[-3:]

        @pl.when(live & (fetched == 0))
        def _():
            fetch(s, pg0, 0, b)

        # the slot's rows in the tile: its one row alone (a decode row, a
        # chunk's first or last), else the whole tile with the other
        # slots' rows masked
        single = r1 - r0 == 1

        def on_rows(fn):
            pl.when(live & single)(lambda: fn(r0 - t_lo, 1))
            pl.when(live & ~single)(lambda: fn(0, tile))

        def stream(row0, n):
            return pl.ds(pl.multiple_of(row0 * heads, heads), n * heads)

        def init(row0, n):
            rows = stream(row0, n)
            m_scr[rows, :] = jnp.full((n * heads, 128), _NEG_INF,
                                      jnp.float32)
            l_scr[rows, :] = jnp.zeros((n * heads, 128), jnp.float32)
            acc_scr[rows, :] = jnp.zeros((n * heads, v_lanes), jnp.float32)

        def own(row0, shape):
            """Which stream rows of [row0, row0 + shape[0]) are the
            slot's, and their absolute positions."""
            local = t_lo + row0 - lo + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0)
            return (local >= 0) & (local < nq), ctx - nq + local

        def attend(grp, b, row0, n):
            rows = stream(row0, n)
            col = pg0 * block_size + grp * width + jax.lax.broadcasted_iota(
                jnp.int32, (n, width), 1)
            mine, qpos = own(row0, (n, width))
            seen = mine & (col <= qpos)
            if window is not None:
                seen = seen & (col > qpos - window)
            if sel_ref is not None:
                chosen = sel_ref[pl.ds(row0, n), pl.ds(
                    pl.multiple_of(grp * width, width), width)]
                seen = seen & (chosen != 0)
            keep = _stacked(seen.astype(jnp.int32), heads) != 0
            keys = kbuf.at[b][...].reshape(width, d)
            sc = mxu_dot(q_ref[rows, :], keys, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * scale
            sc = jnp.where(keep, sc, _NEG_INF)
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # where, not only the shift: while a row has met no key of its
            # own m is the mask value and every masked score would weigh 1
            p = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
            l_new = alpha * l_scr[rows, :1] + jnp.sum(p, axis=1,
                                                     keepdims=True)
            pv = mxu_dot(p.astype(keys.dtype), keys[:, :v_lanes],
                         (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv
            m_scr[rows, :] = jnp.broadcast_to(m_new, (n * heads, 128))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (n * heads, 128))

        def store(row0, n):
            rows = stream(row0, n)
            l = l_scr[rows, :1]
            val = (acc_scr[rows, :]
                   / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            # rows of other slots keep what their own sweep stored (the
            # mask one lane wide: stacked at the output's width from values
            # that do not vary along it, Mosaic's layout pass aborts)
            mine, _ = own(row0, (n, 1))
            o_ref[rows, :] = jnp.where(
                _stacked(mine.astype(jnp.int32), heads) != 0, val,
                o_ref[rows, :])

        on_rows(init)

        def group_body(grp, b):
            # next in flight while this one is computed: the slot's next
            # group, or after its last the next slot's first
            last = grp + 1 == n_grp

            @pl.when(~last | nxt_live)
            def _():
                fetch(jnp.where(last, s + 1, s),
                      jnp.where(last, nxt_pg0, pg0),
                      jnp.where(last, 0, grp + 1), 1 - b)

            wait(b)
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


# jitted on its own so that a model's layers share one trace and one
# lowering of the kernel body (PERF.md, PR 28)
@functools.partial(jax.jit, static_argnames=("scale", "v_lanes", "interpret",
                                             "window"))
def _attend_pallas(q, kc, selected, bt, cu, ctx, num_seqs, scale, v_lanes,
                   interpret, window=None):
    t_total, heads, d = q.shape
    _, bs, _ = kc.shape
    _, mb = bt.shape
    pages = max(1, min(mb, _group_tokens(window) // bs))
    width = pages * bs
    if not interpret and (d % 128 or v_lanes % 128 or width % 128
                          or heads % 16):
        raise NotImplementedError(
            f"the compiled latent kernel needs entries and values "
            f"of a multiple of 128 lanes, page groups of a multiple of 128 "
            f"tokens and heads in 16s: {d} lanes, {v_lanes} value lanes, "
            f"{pages} x {bs} tokens, {heads} heads")
    tile = min(_tile_rows(heads, d, v_lanes, q.dtype.itemsize, width),
               t_total)
    n_qb = -(-t_total // tile)
    t_pad = n_qb * tile

    def tile_of(qb, *_):
        return (qb, 0)

    static = dict(scale=scale, tile=tile, heads=heads, block_size=bs,
                  pages=pages, v_lanes=v_lanes)
    # q's rows in whole tiles
    operands = [jnp.pad(q, ((0, t_pad - t_total), (0, 0), (0, 0))).reshape(
        t_pad * heads, d)]
    in_specs = [pl.BlockSpec((tile * heads, d), tile_of, memory_space=_VMEM)]
    if selected is None:
        # the plain call keeps the name it has had in the device trace
        # since PR 33, the windowed one its own (the metrics tell the calls
        # apart by these)
        static["selected"] = False
        name = "ragged_paged_attention"
        if window is not None:
            static["window"] = window
            name = "ragged_window_latent_attention"
    else:
        name = "ragged_sparse_latent_attention"
        sel_w = -(-mb // pages) * width
        # the mask as 32-bit rows (an int8 tile would be 32 rows), its
        # columns in whole page groups, its rows in whole tiles
        operands.append(jnp.pad(selected.astype(jnp.int32), (
            (0, t_pad - t_total), (0, sel_w - selected.shape[1]))))
        in_specs.append(pl.BlockSpec((tile, sel_w), tile_of,
                                     memory_space=_VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_qb,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile * heads, v_lanes), tile_of,
                               memory_space=_VMEM),
        scratch_shapes=[
            _VMEM((2, pages, bs, d), kc.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            _VMEM((tile * heads, 128), jnp.float32),
            _VMEM((tile * heads, 128), jnp.float32),
            _VMEM((tile * heads, v_lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, **static),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad * heads, v_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(cu, ctx, jnp.reshape(num_seqs, (1,)),
      jnp.maximum(bt, 0).reshape(-1), *operands, kc)
    return out.reshape(t_pad, heads, v_lanes)[:t_total]


def latent_attention(q, k_new, cache, block_tables, cu_seqlens, context_lens,
                     num_seqs, *, v_lanes, window=None, selected=None,
                     scale=None, impl=None):
    """See the module docstring. Returns (out (T, H, v_lanes), cache')."""
    decl = declared()
    if decl is not None and decl[1] is not None:
        raise NotImplementedError(
            "a latent cache has no head axis to shard over a mesh")
    q, cache = jnp.asarray(q), jnp.asarray(cache)
    if selected is not None:
        selected = jnp.asarray(selected)
    t, _, width = q.shape
    bt = jnp.asarray(block_tables).astype(jnp.int32)
    if cache.shape[-1] != width or not 0 < v_lanes <= width:
        raise ValueError(
            f"latent call: q is {width} lanes wide, the cache's entry "
            f"{cache.shape[-1]}, the value its first {v_lanes}")
    if selected is not None and selected.shape != (
            t, bt.shape[1] * cache.shape[1]):
        raise ValueError(
            f"latent call: the selection is a mask a row of q by the "
            f"slot's logical position, ({t}, {bt.shape[1]} x "
            f"{cache.shape[1]}); got {list(selected.shape)}")
    impl = _resolve_impl(impl)
    scale = 1.0 / (width ** 0.5) if scale is None else scale
    cu = jnp.asarray(cu_seqlens).astype(jnp.int32)
    ctx = jnp.asarray(context_lens).astype(jnp.int32)
    ns = jnp.asarray(num_seqs).astype(jnp.int32)
    seg, pos, valid = _token_layout(t, bt.shape[0], cu, ctx, ns)
    if k_new is not None:
        with jax.named_scope("kv_update"):              # the cache scatter
            cache = _write_kv(cache, jnp.asarray(k_new), bt, seg, pos)
    window = int(window) if window else None
    with jax.named_scope("attention"):
        if impl == "ref":
            out = _ragged_attend_ref(q, cache, None, bt, ctx, seg, pos,
                                     valid, scale, v_lanes=int(v_lanes),
                                     selected=selected, window=window)
        else:
            out = _attend_pallas(q, cache, selected, bt, cu, ctx, ns, scale,
                                 int(v_lanes),
                                 interpret=(impl == "interpret"),
                                 window=window)
    return out, cache


def sparse_latent_attention(q, k_new, cache, block_tables, cu_seqlens,
                            context_lens, num_seqs, selected, *, v_lanes,
                            scale=None, impl=None):
    """The call of a layer whose rows select their keys. Returns
    (out (T, H, v_lanes), cache')."""
    return latent_attention(q, k_new, cache, block_tables, cu_seqlens,
                            context_lens, num_seqs, v_lanes=v_lanes,
                            selected=selected, scale=scale, impl=impl)
