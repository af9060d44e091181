"""Ragged paged index scores: the sparse indexer's ``I(t, s) = sum_j
w[t, j] * ReLU(q[t, j] . k[s])`` of every row of the packed token stream
against its slot's cached index keys (``ops/sparse_index.py`` has the
contract; ``ops/pallas/ragged_paged_attention.py`` the stream's).

The grid is the q tiles of the stream (``block_q`` rows). A tile's index
queries arrive STACKED, the ``HI`` heads on the row axis (head j's rows at
``[j * block_q, (j + 1) * block_q)``), so that one MXU product a page group
scores all heads: ``(HI * block_q, DI) x (DI, group)``; ReLU, the per-row
head weight and the sum over the head blocks follow on the VPU. Inside a
tile the kernel walks the slots that have rows in it and, per slot, its
pages up to the causal bound of its last row there, in groups of
``_GROUP_TOKENS`` tokens, double-buffered through VMEM by async copies
(the next group in flight while this one is scored). The output tile
``(block_q, MB * BS)`` float32 starts at ``-inf`` and each (slot, group)
writes the columns its rows see, so what a row does not see stays
``-inf``. Compiled, ``DI % 128 == 0`` and the group is a multiple of 128
tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.common import mxu_dot

__all__ = ["index_scores_pallas"]

_VMEM = pltpu.VMEM
_GROUP_TOKENS = 256
# the (block_q, MB * BS) float32 output tile is 4 MB at 32 rows x 32k
# positions, twice for the pipeline, beside the scores of one group
_VMEM_LIMIT = 64 * 1024 * 1024


def _index_kernel(cu_ref, ctx_ref, ns_ref, bt_ref,      # scalar prefetch
                  q_ref, w_ref, kc_ref, o_ref, kbuf, sem, *,
                  block_q, heads, block_size, pages):
    width = pages * block_size
    s_slots = ctx_ref.shape[0]
    mb = bt_ref.shape[0] // s_slots
    t_lo = pl.program_id(0) * block_q
    t_hi = t_lo + block_q
    ns = ns_ref[0]
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def copy(s, grp, p, b):
        return pltpu.make_async_copy(
            kc_ref.at[bt_ref[s * mb + grp * pages + p]], kbuf.at[b, p],
            sem.at[b])

    def slot_body(s):
        lo = cu_ref[s]
        nq = cu_ref[s + 1] - lo
        r1 = jnp.minimum(lo + nq, t_hi)
        live = r1 > jnp.maximum(lo, t_lo)
        ctx = ctx_ref[s]
        hi = ctx - nq + (r1 - lo) - 1          # absolute pos of row r1-1
        n_pg = jnp.where(live, jnp.clip(hi // block_size + 1, 1, mb), 0)
        n_grp = (n_pg + pages - 1) // pages

        def fetch(grp, b):
            jax.lax.fori_loop(
                0, jnp.minimum(n_pg - grp * pages, pages),
                lambda p, _: (copy(s, grp, p, b).start(), 0)[1], 0)

        def wait(grp, b):
            jax.lax.fori_loop(
                0, jnp.minimum(n_pg - grp * pages, pages),
                lambda p, _: (copy(s, 0, 0, b).wait(), 0)[1], 0)

        @pl.when(live)
        def _():
            fetch(0, 0)

        def group_body(grp, b):
            @pl.when(grp + 1 < n_grp)
            def _():
                fetch(grp + 1, 1 - b)

            wait(grp, b)
            keys = kbuf[b].reshape(width, kbuf.shape[-1])
            dots = mxu_dot(q_ref[...], keys, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)
            dots = jnp.maximum(dots, 0.0) * w_ref[...]
            total = dots[:block_q]
            for j in range(1, heads):
                total = total + dots[j * block_q:(j + 1) * block_q]
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 0)
            col = grp * width + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, width), 1)
            local = t_lo + row - lo
            seen = (local >= 0) & (local < nq) & (col <= ctx - nq + local)
            cols = pl.ds(pl.multiple_of(grp * width, width), width)
            o_ref[:, cols] = jnp.where(seen, total, o_ref[:, cols])
            return 1 - b

        jax.lax.fori_loop(0, n_grp, group_body, jnp.int32(0))
        return s + 1

    # slots are contiguous in the stream: find the tile's first, walk
    # until one starts past the tile
    s0 = jax.lax.while_loop(
        lambda s: (s + 1 < ns) & (cu_ref[s + 1] <= t_lo),
        lambda s: s + 1, jnp.int32(0))
    jax.lax.while_loop(lambda s: (s < ns) & (cu_ref[s] < t_hi), slot_body,
                       s0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores_pallas(q, w, kc, bt, cu, ctx, num_seqs, interpret=False):
    """``q`` (T, HI, DI), ``w`` (T, HI) float32, ``kc`` (blocks,
    block_size, DI), ``bt`` (S, MB). Returns (T, MB * block_size)
    float32 scores, ``-inf`` where a row sees nothing."""
    t_total, heads, di = q.shape
    _, bs, _ = kc.shape
    _, mb = bt.shape
    block_q = 32 if t_total >= 32 else 16
    n_qb = -(-t_total // block_q)
    t_pad = n_qb * block_q
    pages = max(1, min(mb, _GROUP_TOKENS // bs))
    width = pages * bs
    if not interpret and (width % 128 or di % 128):
        raise NotImplementedError(
            f"the compiled index kernel needs page groups of a multiple "
            f"of 128 tokens and keys of a multiple of 128 lanes: "
            f"{pages} x {bs} tokens, {di} lanes")
    out_w = -(-mb // pages) * width
    if t_pad != t_total:
        q = jnp.pad(q, ((0, t_pad - t_total), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, t_pad - t_total), (0, 0)))
    # a tile's heads stacked on the row axis: (tile, head, row)
    q2 = q.reshape(n_qb, block_q, heads, di).transpose(0, 2, 1, 3).reshape(
        n_qb * heads * block_q, di)
    w2 = w.astype(jnp.float32).reshape(n_qb, block_q, heads).transpose(
        0, 2, 1).reshape(n_qb * heads * block_q, 1)

    def tile(qb, *_):
        return (qb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((heads * block_q, di), tile, memory_space=_VMEM),
            pl.BlockSpec((heads * block_q, 1), tile, memory_space=_VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_q, out_w), tile, memory_space=_VMEM),
        scratch_shapes=[
            _VMEM((2, pages, bs, di), kc.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, block_q=block_q, heads=heads,
                          block_size=bs, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, out_w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_index_scores",
    )(cu.astype(jnp.int32), ctx.astype(jnp.int32),
      jnp.reshape(num_seqs.astype(jnp.int32), (1,)),
      jnp.maximum(bt, 0).reshape(-1).astype(jnp.int32), q2, w2, kc)
    return out[:t_total, :mb * bs]
