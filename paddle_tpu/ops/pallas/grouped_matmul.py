"""Grouped matrix product: rows that lie group by group, each group times
its own matrix. The expert layer's two products (``ops/moe.py``).

``x`` (A, K) holds the rows of group 0, then group 1, ...;
``w`` (E, K, N); ``group_sizes`` (E,) int32 with ``sum <= A``. Returns
(A, N) float32: row r of group e is ``x[r] @ w[e]``; rows past
``sum(group_sizes)`` hold nothing meaningful.

Why a kernel of the repo's own. At serving sizes the product is bound by
reading the weights: 64 groups of ~48 rows each read a 5.8 MB matrix, and
a decode-only step (3 rows a group) reads nearly as many. XLA's lowering
of ``jax.lax.ragged_dot`` (a Mosaic kernel with 512-row tiles, each tile
recomputed for every group it overlaps) read 24 % of that bound on a v5e
at the `kimi-vl-a3b-d8.vqa-c32` cell's shapes, the installed megablox
``gmm`` 33 % at its best tiling (PERF.md, PR 33). This one streams: the
work is a list of (row tile, group) items, at most ``A / block_m + E - 1``
of them, in row order; an item multiplies its ``block_m``-row tile of
``x`` by the WHOLE ``(K, block_n)`` slab of its group's matrix (no loop
over K, so no accumulator) and keeps the rows that belong to the group.
Consecutive items share their tile or their group, so each weight slab is
fetched once per tile it touches, the next item's slab in flight while
this one is multiplied (Pallas's own pipeline over scalar-prefetched block
indices). Items past the last repeat it and do nothing.

Selection: ``impl=None`` picks ``"pallas"`` on a TPU backend and
``"xla"`` (``jax.lax.ragged_dot``) elsewhere; ``"interpret"`` runs the
kernel through the Pallas interpreter (slow, tests only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.common import mxu_dot

__all__ = ["grouped_matmul"]

_SLAB_BYTES = 6 * 2 ** 20       # one (K, block_n) weight slab in VMEM


def _work_items(group_sizes, num_rows, block_m):
    """The (row tile, group) pairs that hold at least one row, in row
    order: (count (1,), tile (I,), group (I,), starts (E,), ends (E,));
    entries past ``count`` repeat the last item."""
    e = group_sizes.shape[0]
    max_items = num_rows // block_m + e - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // block_m
    tiles = jnp.where(group_sizes > 0, (ends - 1) // block_m - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    count = item_end[-1]
    i = jnp.minimum(jnp.arange(max_items, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(item_end, i, side="right"),
                        e - 1).astype(jnp.int32)
    tile = first[group] + i - (item_end[group] - tiles[group])
    tile = jnp.clip(tile, 0, num_rows // block_m - 1).astype(jnp.int32)
    return (count.reshape(1).astype(jnp.int32), tile, group,
            starts.astype(jnp.int32), ends.astype(jnp.int32))


def _kernel(count_ref, tile_ref, group_ref, start_ref, end_ref,
            x_ref, w_ref, o_ref, *, block_m):
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _():
        g = group_ref[i]
        row = tile_ref[i] * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        y = mxu_dot(x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        # a row belongs to one group: select, never accumulate, so the
        # tile needs no zeroing and the other groups' rows keep theirs
        o_ref[...] = jnp.where(mine, y, o_ref[...])


def _pick_block_n(k, n, itemsize):
    """The widest multiple of 128 that divides ``n`` and keeps a
    ``(k, block_n)`` slab within ``_SLAB_BYTES`` (all of ``n`` if it has
    no such divisor: tiny test shapes)."""
    fits = [b for b in range(128, n + 1, 128)
            if n % b == 0 and k * b * itemsize <= _SLAB_BYTES]
    return max(fits) if fits else n


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _grouped_matmul_pallas(x, w, group_sizes, block_m, interpret):
    a, k = x.shape
    e, _, n = w.shape
    pad = -a % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rows = a + pad
    items = _work_items(group_sizes.astype(jnp.int32), rows, block_m)
    block_n = _pick_block_n(k, n, w.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // block_n, items[1].shape[0]),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda j, i, c, t, g, s, d: (t[i], 0)),
            pl.BlockSpec((None, k, block_n),
                         lambda j, i, c, t, g, s, d: (g[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, i, c, t, g, s, d: (t[i], j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_m=block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="grouped_matmul",
    )(*items, x, w)
    return out[:a]


def grouped_matmul(x, w, group_sizes, *, impl=None, block_m=128):
    """See the module docstring. Returns (A, N) float32."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        # an ambient ``jax_default_matmul_precision`` reaches float32
        # operands only (``common.mxu_dot``)
        return jax.lax.ragged_dot(
            x, w, group_sizes, preferred_element_type=jnp.float32,
            precision=(None if x.dtype == jnp.float32
                       else jax.lax.Precision.DEFAULT))
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown grouped matmul impl: {impl!r}")
    return _grouped_matmul_pallas(x, w, group_sizes, block_m=block_m,
                                  interpret=(impl == "interpret"))
