"""The blocks ``ids`` of every layer: the one place that turns the
Llama-block engine's per-layer KV cache — a tuple of L
``(NB, BS, KH, D)`` arrays — into the stacked ``(L, n, BS, KH, D)``
frame that the host pool, the tiers and the wire hold, and back. Host
swap, tier moves, KV / prefix export and import and copy-on-write all
move blocks through these two programs (one compile per count of ids,
as the eager indexing they replace had)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["gather_blocks", "scatter_blocks"]


@jax.jit
def gather_blocks(caches, ids):
    """``caches[l][ids]`` of every layer, stacked: ``(L, n, BS, KH, D)``
    in a buffer of its own (the blocks may be rewritten right after).
    Ids may repeat and come in any order."""
    return jnp.stack([c[ids] for c in caches], axis=0)


@functools.partial(jax.jit, donate_argnums=0)
def scatter_blocks(caches, ids, values):
    """Write ``values`` ``(L, n, BS, KH, D)`` into blocks ``ids`` of
    every layer. The caches are DONATED: only the n blocks move, and the
    tuple handed in is dead afterwards — keep the one returned. Ids are
    distinct (callers dedupe: last writer wins)."""
    return tuple(c.at[ids].set(values[i])
                 for i, c in enumerate(caches))
