"""The K/V block frame, and the one owner of it.

A FRAME is the ``(L, n, BS, KH, D)`` array of every layer's K (or V)
for ``n`` blocks. The Llama-block engine keeps no such array on the
device — its caches are two tuples of L ``(NB, BS, KH, D)`` arrays,
donated to the step — but every path that moves blocks outside the
step moves frames: copy-on-write, swap-based preemption, the tiers, the
session tail and the fleet wire. :class:`KVFrames` is all of them, in
three parts:

* **device frames** — :meth:`~KVFrames.gather` / :meth:`~KVFrames.scatter`
  through the two jitted programs below, and :meth:`~KVFrames.copy`,
  the batched copy-on-write;
* **the host pool** — numpy per-TP-shard frames ``(tp, L, NHB, BS,
  KH/tp, D)`` (the swap spill target and the host tier) plus, when
  tiered, the device mirror of the host tier the compiled step
  concatenates with the caches. ONE write path (:meth:`~KVFrames._write`:
  a device gather whose device->host copy starts at once and lands
  before the next read or write, so a reused slot's last writer
  wins) and ONE
  read path (:meth:`~KVFrames._read`, which lands what is pending
  first). Swap-out and tier demote write; swap-in, tier promote and
  the mixed-tier export read. A swap spill first lands the tier moves
  recorded before it, so spills and tier moves share one event order;
* **the wire codec** — :meth:`~KVFrames.pack` / :meth:`~KVFrames.unpack`:
  K shard frames, then V shard frames, CRC-32 over the whole payload,
  the layout stanza that lets an importer of another TP degree
  reshard.

The engine owns one instance; the scheduler's ``kv_swapper`` seam and
the tier store call it, and neither touches the pool or the caches.
"""
from __future__ import annotations

import functools
import itertools
import zlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KVFrames", "gather_blocks", "scatter_blocks"]


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


def _start_d2h(bufs):
    """Start the device->host copy of every buffer, without waiting."""
    for buf in bufs:
        start = getattr(buf, "copy_to_host_async", None)
        if start is not None:
            start()
    return bufs


def _to_host(*bufs) -> List[np.ndarray]:
    """Host copies of device frames: every device->host copy starts
    before the first one is waited on."""
    return [np.asarray(b) for b in _start_d2h(bufs)]  # tpulint: disable=host-sync-in-traced (the frame's one device->host land: swap spills, tier demotes and the export gather, a handful of blocks off the step's critical path)


class KVFrames:
    """Device frames, the host pool and the wire codec of one engine's
    K/V caches. The caches stay on the engine (``engine._kcs`` /
    ``engine._vcs``: the step donates and reassigns them); this object
    reads and writes them there."""

    def __init__(self, engine, num_host_blocks: int, sharding=None,
                 mirror=None):
        self._eng = engine
        self._bm = engine.block_manager
        self.layout = engine.kv_layout
        self._sharding = sharding
        # the tiered step's device copy of the host tier: a (K, V) pair
        # of per-layer (NHB, BS, KH, D) tuples, None untiered
        self.mirror = mirror
        # the host write in flight: (slots, K frame, V frame), the
        # frames still device-side
        self._pending: Optional[tuple] = None
        self._host = None
        if engine._kcs is not None and num_host_blocks > 0:
            L, (_, bs, kh, d) = len(engine._kcs), engine._kcs[0].shape
            tp = self.layout.size
            shape = (tp, L, num_host_blocks, bs, kh // tp, d)
            self._host = (np.zeros(shape, self.dtype),
                          np.zeros(shape, self.dtype))

    # -- device frames ----------------------------------------------------
    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._eng._kcs[0].dtype)

    def shape(self, n: int) -> tuple:
        """``(L, n, BS, KH, D)``: the frame of ``n`` blocks."""
        kcs = self._eng._kcs
        return (len(kcs), n, *kcs[0].shape[1:])

    def gather(self, ids):
        """The K and V frames of device blocks ``ids``, each in a device
        buffer of its own."""
        eng = self._eng
        ids = np.asarray(ids, np.int32)
        return gather_blocks(eng._kcs, ids), gather_blocks(eng._vcs, ids)

    def scatter(self, ids, k_frame, v_frame):
        """Write frames into device blocks ``ids`` of every layer: the
        caches are donated to the write, so only the n blocks move."""
        eng = self._eng
        ids = np.asarray(ids, np.int32)
        eng._kcs = scatter_blocks(eng._kcs, ids, k_frame)
        eng._vcs = scatter_blocks(eng._vcs, ids, v_frame)
        self._pin_caches()

    def copy(self, pairs: Sequence[Tuple[int, int]]):
        """Copy-on-write: block ``src`` -> block ``dst`` for every pair,
        one batched gather and one batched scatter."""
        if pairs:
            self.scatter([dst for _, dst in pairs],
                         *self.gather([src for src, _ in pairs]))

    def _pin_caches(self):
        """Re-commit the caches (and the mirror) to the TP cache
        sharding after a write outside the step: it may hand back a
        differently-sharded result, and a drifted layout would silently
        recompile the ONE step the engine promises. No-op unsharded."""
        if self._sharding is not None:
            eng = self._eng
            eng._kcs, eng._vcs, self.mirror = jax.device_put(
                (eng._kcs, eng._vcs, self.mirror), self._sharding)

    # -- the host pool ----------------------------------------------------
    def _write(self, slots, dev_ids):
        """THE write path: host ``slots`` <- device blocks ``dev_ids``.
        The gather reads the blocks now (they may be rewritten by the
        very next step); the bytes land in the pool at the next read or
        the next write, whichever comes first, so at most one write is
        in flight and each lands after every write before it."""
        self._land()
        k, v = _start_d2h(self.gather(dev_ids))
        self._pending = (np.asarray(slots, np.int32), k, v)
        return k, v

    def _land(self):
        """Land the write in flight, if any."""
        if self._pending is not None:
            slots, k, v = self._pending
            self._pending = None
            for h, f in zip(self._host, _to_host(k, v)):
                h[:, :, slots] = self.layout.shard_frames(f)

    def _read(self, slots):
        """THE read path: host ``slots`` -> unsharded K and V frames."""
        self._land()
        return tuple(self.layout.unshard_frames(h[:, :, slots])
                     for h in self._host)

    def copy_out(self, request, dev_table: List[int],
                 host_table: List[int]):
        """Swap-out (the scheduler's ``kv_swapper`` seam): spill the
        blocks the host table covers — the device table may hold one
        more, a decode-step slot claimed before the eviction. Tier moves
        recorded before the eviction land first, so the spill is the
        later writer of any slot they share."""
        self.apply_tier_moves()
        self._write(host_table, dev_table[:len(host_table)])

    def copy_in(self, request, host_table: List[int],
                dev_table: List[int]):
        """Swap-in: host slots back into fresh device blocks."""
        self.scatter(dev_table, *self._read(host_table))

    def apply_tier_moves(self) -> int:
        """Drain the BlockManager's tier-move ledger and move the bytes,
        in record order (a slot or block freed by one move may be the
        destination of a later one). A demote writes the host pool and
        the mirror; a promote reads the pool. Returns moves applied."""
        moves = self._bm.take_tier_moves()
        for kind, run in itertools.groupby(moves, key=lambda m: m[0]):
            # the last write per destination: a batched write with
            # repeated indices must match sequential last-writer-wins
            last = {dst: src for _, src, dst in run}
            src, dst = list(last.values()), list(last)
            if kind == "demote":
                k, v = self._write(dst, src)
                ids = np.asarray(dst, np.int32)
                self.mirror = (scatter_blocks(self.mirror[0], ids, k),
                               scatter_blocks(self.mirror[1], ids, v))
                self._pin_caches()
            else:
                self.scatter(dst, *self._read(src))
        return len(moves)

    def fetch(self, table: Sequence[int]):
        """Host K and V frames of the entries of a block table. A tiered
        table may hold VIRTUAL entries whose bytes live in the host
        pool: those read straight from it, the rest from the device."""
        self.apply_tier_moves()
        bm = self._bm
        host = [i for i, b in enumerate(table) if bm.is_host_entry(b)]
        if not host:
            return tuple(_to_host(*self.gather(table)))
        dev = [i for i, b in enumerate(table) if not bm.is_host_entry(b)]
        out = tuple(np.empty(self.shape(len(table)), self.dtype)
                    for _ in range(2))
        if dev:
            got = _to_host(*self.gather([table[i] for i in dev]))
            for o, f in zip(out, got):
                o[:, dev] = f
        got = self._read([bm.host_slot_of(table[i]) for i in host])
        for o, f in zip(out, got):
            o[:, host] = f
        return out

    # -- the wire codec ---------------------------------------------------
    def pack(self, table: Sequence[int], **meta):
        """``(meta, payload)`` of the blocks of ``table``: the K shard
        frames, then the V shard frames, in mesh order (one frame each
        unsharded), ``meta`` followed by the geometry, the layout stanza
        and a CRC-32 of the whole payload."""
        k, v = self.fetch(table)
        k_bytes = self.layout.shard_frames(k).tobytes()
        payload = k_bytes + self.layout.shard_frames(v).tobytes()
        meta.update(
            blocks=len(table), block_size=int(k.shape[2]),
            shape=list(k.shape), dtype=str(k.dtype),
            k_bytes=len(k_bytes), layout=self.layout.to_meta(),
            crc32=zlib.crc32(payload) & 0xFFFFFFFF)
        return meta, payload

    def unpack(self, meta: dict, payload: bytes, nblocks: int, who: str):
        """Check a shipped payload against ``nblocks`` blocks of this
        engine's frame and land it: ``(k, v, src_layout)`` with ``k``
        and ``v`` global host frames in this engine's orientation. Every
        mismatch is a ``ValueError`` whose message starts with ``who``."""
        want = list(self.shape(nblocks))
        bs = want[2]
        if int(meta.get("block_size", -1)) != bs:
            raise ValueError(
                f"{who} block_size {meta.get('block_size')} != {bs}")
        if list(meta.get("shape", ())) != want or \
                int(meta.get("blocks", -1)) != nblocks:
            raise ValueError(
                f"{who} KV shape {meta.get('shape')} != expected {want}")
        if str(meta.get("dtype")) != str(self.dtype):
            raise ValueError(f"{who} dtype {meta.get('dtype')} != cache "
                             f"dtype {self.dtype}")
        k_bytes = int(meta.get("k_bytes", -1))
        want_bytes = int(np.prod(want)) * self.dtype.itemsize
        if k_bytes != want_bytes or len(payload) != 2 * want_bytes:
            raise ValueError(
                f"{who} payload {len(payload)}B (k={k_bytes}) != "
                f"2x{want_bytes}B")
        if zlib.crc32(payload) & 0xFFFFFFFF != int(meta.get("crc32", -1)):
            raise ValueError(f"{who} KV failed its checksum — payload "
                             f"corrupt, refusing the import")
        src = self.wire_layout(meta, want)
        return (self._land_wire(payload, 0, src, want),
                self._land_wire(payload, k_bytes, src, want), src)

    def wire_layout(self, meta: dict, global_shape):
        """The layout a shipped payload's frames are in. Absent stanza =
        the pre-TP flat format (one replicated frame). A malformed or
        non-fitting layout is a clean ``ValueError`` rejection, same as
        any geometry mismatch."""
        from paddle_tpu.distributed.redistribute import Layout

        lm = meta.get("layout")
        if lm is None:
            return Layout.tp_sharded(len(global_shape), 3, 1)
        try:
            src = Layout.from_meta(lm)
            src.validate_shape(global_shape)
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(
                f"shipped KV layout {lm!r} does not fit shape "
                f"{list(global_shape)}: {e}") from e
        return src

    def _land_wire(self, payload: bytes, offset: int, src_layout,
                   global_shape) -> np.ndarray:
        """Parse per-shard wire frames into one global host array in
        THIS engine's orientation. A ship from a replica of another TP
        degree reshards through ``redistribute`` — the one primitive the
        SPMD step and checkpoint restore use — instead of being
        rejected."""
        dtype = self.dtype
        local = src_layout.local_shape(global_shape)
        n = int(np.prod(local))
        frames = [np.frombuffer(payload, dtype=dtype,
                                offset=offset + i * n * dtype.itemsize,
                                count=n).reshape(local)
                  for i in range(src_layout.size)]
        if src_layout != self.layout:
            from paddle_tpu.distributed.redistribute import (
                redistribute_host,
            )

            frames = redistribute_host(frames, src_layout, self.layout,
                                       global_shape)
        return self.layout.assemble(frames, global_shape)
