"""Device-resident async input pipeline.

The profiler's phase breakdown on the 1B-GPT config (July-2026 chip run) showed
more device time in copies than in compute (copy_frac 0.545): the
compiled step was waiting on host->device transfers that could have
overlapped the previous step, and each batch array paid its own
per-argument dispatch cost. ``DevicePrefetcher`` closes both gaps:

* **Overlap**: a background thread pulls batches from the host loader
  and issues the host->device transfer ``depth`` batches ahead, so by
  the time the train loop asks for batch N its arrays are already
  device-committed (device work releases the GIL inside XLA, so the
  producer genuinely runs during compute).
* **Coalescing**: all arrays of a batch that share a dtype are packed
  into ONE contiguous staging buffer on the host and shipped with ONE
  ``device_put`` (one marshaled argument instead of dozens), then
  unpacked on-device by a cached jitted slice/reshape program (the
  staging allocation is freed once its reference drops after the
  unpack; see ``_unpack_fn`` for why it is not donated).
* **Placement**: with ``mesh``/``placements`` the transfer lands
  directly in the requested ``NamedSharding`` (the ``distributed``
  placement helpers), e.g. batch-dim sharded over the ``dp`` mesh axis —
  no replicate-then-reshard copy. Only genuinely Shard-placed leaves
  take a direct per-leaf transfer; replicate-placed leaves (labels,
  masks) still coalesce through a mesh-replicated staging buffer.

Consumed via ``DataLoader(..., use_device_prefetch=True)`` or
``prefetch_to_device(loader, depth=2)`` around any iterable of batches.
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Iterable

import numpy as np

import jax

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.profiler import span

__all__ = ["DevicePrefetcher", "prefetch_to_device"]


def _to_host(leaf):
    """Array leaf -> numpy with the dtype the device array will carry
    (x64 canonicalization happens on host so the coalesced staging
    buffer is byte-identical to what lands on device). Non-array leaves
    (strings, python objects — e.g. filename metadata from a custom
    collate) return None: they pass through the prefetcher untouched,
    matching the plain DataLoader path."""
    if isinstance(leaf, Tensor):
        # not .numpy(): that widens bf16 to f32; ml_dtypes keeps the
        # staging buffer in the array's own dtype
        leaf = np.asarray(leaf._data)
    elif isinstance(leaf, jax.Array):
        leaf = np.asarray(leaf)
    elif isinstance(leaf, (np.ndarray, np.generic)):
        leaf = np.asarray(leaf)
    else:
        return None
    kind = leaf.dtype.kind
    if kind not in "biufc" and not (
            kind == "V" and leaf.dtype.names is None):
        # strings/objects/structured arrays pass through; unnamed void
        # dtypes are the ml_dtypes extended floats (bfloat16, fp8),
        # which ARE stageable
        return None
    canon = jax.dtypes.canonicalize_dtype(leaf.dtype)
    if leaf.dtype != canon:
        leaf = leaf.astype(canon)
    return leaf


# ---------------------------------------------------------------------------
# coalesced staging: one transfer per dtype, on-device unpack
# ---------------------------------------------------------------------------
from collections import OrderedDict  # noqa: E402

_unpack_cache: "OrderedDict" = OrderedDict()
# LRU bound: variable-shape workloads (length-bucketed NLP batches) must
# not accumulate one compiled unpack program per shape set forever.
# Locked: every DevicePrefetcher's producer thread touches this cache
# (jax.jit() construction under the lock is cheap — compilation happens
# at the call site).
_UNPACK_CACHE_MAX = 128
_unpack_lock = threading.Lock()


def _unpack_fn(dtype_str: str, shapes: tuple):
    """Jitted (staging buffer) -> tuple of reshaped static slices. Not
    donated: XLA cannot alias sub-buffer views anyway, and jax's "donated
    buffer not usable" warning would have to be suppressed via
    process-global (thread-unsafe) warning state; the staging array is
    freed as soon as its Python reference drops after the call."""
    key = (dtype_str, shapes)
    with _unpack_lock:
        fn = _unpack_cache.get(key)
        if fn is not None:
            _unpack_cache.move_to_end(key)
            return fn
        sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
        offsets = np.concatenate([[0], np.cumsum(sizes)]).tolist()

        def unpack(buf):
            return tuple(
                jax.lax.slice(buf, (offsets[i],),
                              (offsets[i] + sizes[i],))
                .reshape(shapes[i])
                for i in range(len(shapes)))

        fn = jax.jit(unpack)
        _unpack_cache[key] = fn
        while len(_unpack_cache) > _UNPACK_CACHE_MAX:
            _unpack_cache.popitem(last=False)
        return fn


def _stage_batch(np_leaves, coalesce_target, direct_targets,
                 singleton_targets=None):
    """Transfer one batch's numpy leaves and return the device arrays
    (committed) in leaf order.

    Leaves with a ``direct_targets`` entry (genuinely sharded leaves,
    or everything when coalescing is off) go through their own
    device_put. The rest are coalesced per dtype: one contiguous host
    staging array, one device_put onto ``coalesce_target`` (a device,
    or a rank-1 replicated NamedSharding under a mesh), one on-device
    unpack. A dtype group of one skips packing and uses the leaf's
    ``singleton_targets`` entry (the rank-1 staging sharding is invalid
    for a rank-0 leaf)."""
    out = [None] * len(np_leaves)
    groups: dict = {}
    for i, leaf in enumerate(np_leaves):
        if direct_targets is not None and direct_targets[i] is not None:
            out[i] = jax.device_put(leaf, direct_targets[i])
            continue
        groups.setdefault(str(leaf.dtype), []).append(i)
    for dtype_str, idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = jax.device_put(
                np_leaves[i],
                singleton_targets[i] if singleton_targets is not None
                else coalesce_target)
            continue
        shapes = tuple(tuple(np_leaves[i].shape) for i in idxs)
        staging = np.concatenate(
            [np_leaves[i].ravel() for i in idxs])
        staged = jax.device_put(staging, coalesce_target)
        views = _unpack_fn(dtype_str, shapes)(staged)
        for i, v in zip(idxs, views):
            out[i] = v
    return out


# ---------------------------------------------------------------------------
# the prefetcher
# ---------------------------------------------------------------------------
class DevicePrefetcher:
    """Wraps an iterable of batches (trees of numpy arrays / Tensors) and
    yields the same trees with every array leaf replaced by a
    device-committed Tensor, transferred ``depth`` batches ahead on a
    background thread.

    ``mesh`` + ``placements`` route every leaf into the corresponding
    ``NamedSharding`` (see ``paddle_tpu.distributed``); placements whose
    sharded tensor dim does not exist on a leaf (e.g. ``Shard(1)`` on a
    1-D label array) fall back to replicated for that leaf.
    """

    def __init__(self, loader: Iterable, depth: int = 2, *,
                 mesh=None, placements=None, device=None,
                 coalesce: bool = True):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._loader = loader
        self._depth = depth
        self._mesh = mesh
        self._placements = placements
        self._coalesce = coalesce
        if device is None:
            from paddle_tpu.core.place import _default_place

            device = _default_place().jax_device()
        self._device = device
        if (mesh is None) != (placements is None):
            raise ValueError(
                "mesh and placements must be given together")
        if mesh is not None:
            from paddle_tpu.distributed.api import _normalize_placements

            self._placements = _normalize_placements(mesh, placements)
        self._sharding_by_ndim: dict = {}  # ndim -> (sharding, has_shard)
        self._replicated_by_ndim: dict = {}  # ndim -> replicated fallback
        self._staging_sh = None  # replicated 1-D staging NamedSharding

    def __len__(self):
        return len(self._loader)

    def _sharding_for(self, leaf):
        """(NamedSharding, genuinely-sharded?) for one leaf. Cached per
        leaf rank: placements are fixed at construction and only the
        rank-degrade step varies per leaf."""
        entry = self._sharding_by_ndim.get(leaf.ndim)
        if entry is None:
            from paddle_tpu.distributed.mesh import Replicate, Shard

            def out_of_rank(p):
                # a placement sharding a dim the leaf doesn't have
                # (labels are often lower-rank than inputs) degrades to
                # Replicate; negative dims count from the trailing axis
                if not isinstance(p, Shard):
                    return False
                d = p.dim if p.dim >= 0 else p.dim + leaf.ndim
                return d < 0 or d >= leaf.ndim

            safe = [Replicate() if out_of_rank(p) else p
                    for p in self._placements]
            entry = (self._mesh.sharding_for(safe, leaf.ndim),
                     any(isinstance(p, Shard) for p in safe))
            self._sharding_by_ndim[leaf.ndim] = entry
        return entry

    def _staging_sharding(self):
        """Fully-replicated NamedSharding for the 1-D staging buffer:
        replicate-placed leaves still coalesce under a mesh."""
        if self._staging_sh is None:
            from paddle_tpu.distributed.mesh import Replicate

            self._staging_sh = self._mesh.sharding_for(
                [Replicate()] * self._mesh.ndim, 1)
        return self._staging_sh

    def _replicated_for(self, ndim):
        """Fully-replicated NamedSharding at a leaf's rank — the
        fallback for leaves that cannot take their Shard placement."""
        sh = self._replicated_by_ndim.get(ndim)
        if sh is None:
            from paddle_tpu.distributed.mesh import Replicate

            sh = self._mesh.sharding_for(
                [Replicate()] * self._mesh.ndim, ndim)
            self._replicated_by_ndim[ndim] = sh
        return sh

    def _divisible(self, leaf):
        """Whether every Shard placement divides the leaf's dim evenly —
        false for the tail batch of a drop_last=False epoch, which must
        degrade to replicated instead of crashing the producer."""
        from paddle_tpu.distributed.mesh import Shard

        for mesh_dim, p in enumerate(self._placements):
            if not isinstance(p, Shard):
                continue
            d = p.dim if p.dim >= 0 else p.dim + leaf.ndim
            if 0 <= d < leaf.ndim and \
                    leaf.shape[d] % self._mesh.shape[mesh_dim]:
                return False
        return True

    def _transfer(self, batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        out = list(leaves)  # non-array leaves pass through untouched
        idxs, np_leaves = [], []
        for i, lf in enumerate(leaves):
            h = _to_host(lf)
            if h is not None:
                idxs.append(i)
                np_leaves.append(h)
        direct = None
        singleton = None
        target = self._device
        if self._mesh is not None:
            # Shard-placed leaves need their own layout; Replicate-only
            # leaves still amortize marshaling through the packed path
            # (their own rank's sharding when a dtype group is a
            # singleton — valid for rank-0 where the staging one isn't).
            # coalesce=False forces the direct path for every leaf.
            direct, singleton = [], []
            for lf in np_leaves:
                sh, has_shard = self._sharding_for(lf)
                if has_shard and not self._divisible(lf):
                    # tail batch (drop_last=False): not evenly shardable
                    # — land it replicated; the compiled step reshards
                    sh, has_shard = self._replicated_for(lf.ndim), False
                direct.append(sh if has_shard or not self._coalesce
                              else None)
                singleton.append(sh)
            target = self._staging_sharding()
        elif not self._coalesce:
            direct = [self._device] * len(np_leaves)
        dev = _stage_batch(np_leaves, target, direct, singleton)
        for i, d in zip(idxs, dev):
            out[i] = Tensor._from_data(d)
        return jax.tree_util.tree_unflatten(treedef, out)

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        stop = threading.Event()
        END = object()

        def producer():
            try:
                it = iter(self._loader)
                for n in itertools.count():
                    with span("prefetch.load", batch=n):
                        batch = next(it, END)
                    if batch is END:
                        break
                    if stop.is_set():
                        return
                    with span("prefetch.stage", batch=n):
                        item = ("ok", self._transfer(batch))
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                payload = ("end", END)
            except BaseException as e:  # propagate to the consumer
                payload = ("err", e)
            while not stop.is_set():
                try:
                    q.put(payload, timeout=0.1)
                    return
                except queue.Full:
                    continue

        t = threading.Thread(target=producer, daemon=True,
                             name="DevicePrefetcher")
        t.start()
        try:
            while True:
                with span("prefetch.wait"):
                    kind, item = q.get()
                if kind == "end":
                    return
                if kind == "err":
                    raise item
                yield item
        finally:
            # deterministic shutdown: an abandoned iterator must not
            # leave the producer mid-transfer at interpreter teardown
            stop.set()
            t.join(timeout=10.0)


def prefetch_to_device(loader: Iterable, depth: int = 2, *,
                       mesh=None, placements=None, device=None,
                       coalesce: bool = True) -> DevicePrefetcher:
    """Wrap ``loader`` so its batches arrive on device ``depth`` steps
    ahead of consumption (see ``DevicePrefetcher``)."""
    return DevicePrefetcher(loader, depth, mesh=mesh,
                            placements=placements, device=device,
                            coalesce=coalesce)
