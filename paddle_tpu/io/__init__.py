"""Data loading (reference: python/paddle/io/).

Host-side input pipeline that keeps the TPU fed: Dataset/Sampler abstractions
match the reference; DataLoader batches on host (numpy), optionally with a
background prefetch thread (the role of the reference's buffered reader +
LoDTensorBlockingQueue, python/paddle/io/dataloader/dataloader_iter.py:114).
num_workers > 0 forks worker processes that fetch + collate to numpy and
ship batches through an mp queue with a deterministic reorder buffer
(reference dataloader/worker.py); thread-prefetch additionally overlaps
host batching with device compute since device work releases the GIL
inside XLA. ``use_device_prefetch=True`` goes one stage further: the
whole pipeline stays numpy until ``io.prefetch.DevicePrefetcher`` ships
each batch to the device ``depth`` steps ahead as one coalesced
transfer per dtype (see that module for the copy-fraction story).
"""
from __future__ import annotations

import itertools
import queue
import threading

import numpy as np

from paddle_tpu.core.tensor import Tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ConcatDataset",
           "ChainDataset", "ComposeDataset", "SubsetRandomSampler", "Subset", "random_split", "DataLoader",
           "BatchSampler", "Sampler", "SequenceSampler", "RandomSampler",
           "DistributedBatchSampler", "WeightedRandomSampler",
           "get_worker_info", "default_collate_fn",
           "DevicePrefetcher", "prefetch_to_device"]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset is not indexable")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = list(itertools.accumulate(len(d) for d in self.datasets))

    def __len__(self):
        return self.cum[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        for i, c in enumerate(self.cum):
            if idx < c:
                prev = self.cum[i - 1] if i else 0
                return self.datasets[i][idx - prev]
        raise IndexError(idx)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        for d in self.datasets:
            yield from d


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    n = len(dataset)
    if abs(sum(lengths) - 1.0) < 1e-6 and all(
            isinstance(x, float) for x in lengths):
        lengths = [int(x * n) for x in lengths]
        lengths[-1] = n - sum(lengths[:-1])
    perm = np.random.permutation(n)
    out, off = [], 0
    for ln in lengths:
        out.append(Subset(dataset, perm[off:off + ln].tolist()))
        off += ln
    return out


# ---------------------------------------------------------------------------
# samplers (reference: python/paddle/io/dataloader/sampler.py, batch_sampler.py)
# ---------------------------------------------------------------------------
class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards indices across data-parallel ranks (reference:
    python/paddle/io/dataloader/dist_batch_sampler.py)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from paddle_tpu.distributed import env as dist_env

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = (num_replicas if num_replicas is not None
                       else dist_env.get_world_size())
        self.local_rank = rank if rank is not None else dist_env.get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        n = len(dataset)
        self.num_samples = int(np.ceil(n / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: (self.total_size - len(indices))]
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


# ---------------------------------------------------------------------------
# collate + loader
# ---------------------------------------------------------------------------
def _collate_np(batch):
    """Numpy-only collate used inside worker processes (they must not
    create device arrays: the forked child would share the parent's
    accelerator runtime/sockets)."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(_collate_np([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: _collate_np([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        raise RuntimeError(
            "dataset __getitem__ returned a device Tensor inside a "
            "DataLoader worker process; return numpy arrays (or python "
            "scalars) when num_workers > 0 — a forked worker must not "
            "drive the parent's accelerator runtime")
    return np.stack([np.asarray(s) for s in batch])


def _tree_to_host(x):
    """Tree -> host numpy, dtype-preserving: Tensor.numpy() widens bf16
    to f32, which would silently change the batch dtype (and force a
    train-step retrace) on the device-prefetch path; np.asarray of the
    jax array keeps bf16 via ml_dtypes."""
    if isinstance(x, Tensor):
        return np.asarray(x._data)
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_to_host(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_to_host(v) for k, v in x.items()}
    return x


def _tree_to_tensor(x):
    if isinstance(x, np.ndarray):
        return Tensor(x)
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_to_tensor(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_to_tensor(v) for k, v in x.items()}
    return x


def _worker_loop(wid, nw, dataset, indexed_batches, batch_size, drop_last,
                 collate_fn, worker_init_fn, result_q):
    """Body of one DataLoader worker process (reference worker.py
    _worker_loop): fetch, collate to numpy, ship (batch_id, data)."""
    global _worker_info
    try:
        _worker_info = _WorkerInfo(id=wid, num_workers=nw, dataset=dataset)
        if worker_init_fn is not None:
            worker_init_fn(wid)
        collate = _collate_np if collate_fn is default_collate_fn \
            else (lambda b: _tree_to_host(collate_fn(b)))
        if indexed_batches is None:
            # iterable dataset: this worker consumes its own iterator
            batch = []
            bid = wid
            for item in dataset:
                batch.append(item)
                if len(batch) == batch_size:
                    result_q.put(("ok", (bid, collate(batch))))
                    bid += nw
                    batch = []
            if batch and not drop_last:
                result_q.put(("ok", (bid, collate(batch))))
        else:
            for bid, idxs in indexed_batches:
                result_q.put(
                    ("ok", (bid, collate([dataset[i] for i in idxs]))))
        result_q.put(("end", wid))
    except BaseException:
        import traceback

        result_q.put(("err", traceback.format_exc()))


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s.numpy()) for s in batch]))
    arr = np.stack([np.asarray(s) for s in batch])
    return Tensor(arr)


class _WorkerInfo:
    def __init__(self, id=0, num_workers=1, dataset=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_device_prefetch=False,
                 device_prefetch_depth=2, prefetch_mesh=None,
                 prefetch_placements=None):
        if prefetch_factor < 1:
            raise ValueError(
                f"prefetch_factor must be >= 1, got {prefetch_factor}")
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        self.use_device_prefetch = use_device_prefetch
        self.device_prefetch_depth = device_prefetch_depth
        self.prefetch_mesh = prefetch_mesh
        self.prefetch_placements = prefetch_placements
        self.worker_init_fn = worker_init_fn
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _raw_iter(self, collate=None):
        collate = collate or self.collate_fn
        if self._iterable_mode:
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    yield collate(batch)
                    batch = []
            if batch and not self.drop_last:
                yield collate(batch)
        else:
            for idx_batch in self.batch_sampler:
                yield collate([self.dataset[i] for i in idx_batch])

    def _numpy_batches(self):
        """In-process batches as host numpy trees — the device-prefetch
        source (the num_workers > 0 source is _multiprocess_iter with
        to_tensor=False, started from the consuming thread in __iter__).
        Keeping the pipeline in numpy until DevicePrefetcher's one
        coalesced transfer avoids the collate path's per-array
        device_put."""
        if self.collate_fn is default_collate_fn:
            # unlike the worker-process path, in-process samples MAY be
            # device Tensors — fetch them to host before packing
            collate = lambda b: _collate_np(  # noqa: E731
                [_tree_to_host(s) for s in b])
        else:
            collate = lambda b: _tree_to_host(self.collate_fn(b))  # noqa: E731
        yield from self._raw_iter(collate)

    def __iter__(self):
        if self.use_device_prefetch:
            if self.num_workers > 0:
                # fork the worker processes from the CONSUMING thread,
                # not the prefetch producer thread: forking while
                # another thread sits inside an XLA dispatch (the
                # steady-state overlap the prefetcher creates) can
                # leave the child holding dead locks
                end = object()
                src = self._multiprocess_iter(to_tensor=False)
                first = next(src, end)
                batches = (itertools.chain([first], src)
                           if first is not end else iter(()))
            else:
                batches = self._numpy_batches()
            yield from DevicePrefetcher(
                batches, depth=self.device_prefetch_depth,
                mesh=self.prefetch_mesh,
                placements=self.prefetch_placements)
            return
        if self.num_workers > 0:
            yield from self._multiprocess_iter()
            return
        if not self.use_buffer_reader:
            yield from self._raw_iter()
            return
        # background prefetch thread (buffered-reader role); capacity is
        # per-worker depth (reference prefetch_factor semantics) — this
        # path always has exactly one in-process producer
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_factor)
        sentinel = object()
        err = []

        def worker():
            try:
                for item in self._raw_iter():
                    q.put(item)
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        if err:
            raise err[0]

    # -- multiprocess workers (reference dataloader/worker.py) ------------
    def _multiprocess_iter(self, to_tensor=True):
        """num_workers > 0: forked worker processes fetch + collate
        batches to NUMPY (workers must not touch the accelerator
        runtime); the main process reorders results by batch index so
        iteration order is deterministic, then materializes Tensors
        (``to_tensor=False`` keeps numpy — the device-prefetch source).
        Reference: dataloader_iter.py _DataLoaderIterMultiProcess +
        worker.py (the C++ LoDTensorBlockingQueue role is played by the
        mp.SimpleQueue + reorder buffer)."""
        import multiprocessing as mp

        materialize = _tree_to_tensor if to_tensor else (lambda x: x)

        ctx = mp.get_context("fork")
        dataset = self.dataset
        if isinstance(dataset, TensorDataset):
            # device-backed tensors must be materialized in the PARENT:
            # the forked child must not drive the inherited PJRT client
            dataset = TensorDataset([
                np.asarray(t.numpy()) if isinstance(t, Tensor) else t
                for t in dataset.tensors])
        if self._iterable_mode:
            # each worker iterates its own dataset copy with worker_info
            # set; batches are interleaved worker-major (reference
            # iterable semantics: sharding is the dataset's job)
            idx_queues = None
            n_batches = None
        else:
            batches = list(self.batch_sampler)
            n_batches = len(batches)
        nw = self.num_workers
        # transport: native C++ shared-memory ring buffer (one memcpy per
        # batch; the reference's LoDTensorBlockingQueue role) with
        # use_shared_memory — a build failure raises, it does not
        # quietly change the transport — else an mp.Queue (pickle)
        if self.use_shared_memory:
            from paddle_tpu.io.shm_queue import ShmQueue

            result_q = ShmQueue()
        else:
            # per-worker prefetch depth (reference prefetch_factor
            # semantics): a full queue backpressures the workers
            result_q = ctx.Queue(
                maxsize=self.prefetch_factor * max(1, nw))
        workers = []

        def _get():
            # liveness-aware get: a worker killed by the OS (OOM/segv)
            # never posts 'end', so a bare blocking get would hang the job
            import queue as _q

            while True:
                try:
                    return result_q.get(timeout=1.0)
                except _q.Empty:
                    for p in workers:
                        if p.exitcode not in (None, 0):
                            raise RuntimeError(
                                f"DataLoader worker died with exit code "
                                f"{p.exitcode} (killed by the OS?)")
                except EOFError:
                    # shm transport: closed by a recovered dead-writer
                    raise RuntimeError(
                        "DataLoader shm queue closed unexpectedly (a "
                        "worker died mid-record?)")
        try:
            for wid in range(nw):
                if self._iterable_mode:
                    wargs = (wid, nw, dataset, None, self.batch_size,
                             self.drop_last, self.collate_fn,
                             self.worker_init_fn, result_q)
                else:
                    my = batches[wid::nw]
                    my_ids = list(range(wid, n_batches, nw))
                    wargs = (wid, nw, dataset, list(zip(my_ids, my)),
                             None, None, self.collate_fn,
                             self.worker_init_fn, result_q)
                p = ctx.Process(target=_worker_loop, args=wargs,
                                daemon=True)
                p.start()
                workers.append(p)
            done = 0
            if self._iterable_mode:
                buf = []
                while done < nw:
                    kind, payload = _get()
                    if kind == "err":
                        raise RuntimeError(
                            f"DataLoader worker failed:\n{payload}")
                    if kind == "end":
                        done += 1
                        continue
                    yield materialize(payload[1])
            else:
                pending = {}
                nxt = 0
                while nxt < n_batches:
                    if nxt in pending:
                        yield materialize(pending.pop(nxt))
                        nxt += 1
                        continue
                    kind, payload = _get()
                    if kind == "err":
                        raise RuntimeError(
                            f"DataLoader worker failed:\n{payload}")
                    if kind == "end":
                        done += 1
                        if done == nw and nxt < n_batches and \
                                nxt not in pending:
                            missing = [i for i in range(nxt, n_batches)
                                       if i not in pending]
                            if missing:
                                raise RuntimeError(
                                    f"workers exited with batches "
                                    f"{missing[:4]}... missing")
                        continue
                    pending[payload[0]] = payload[1]
        finally:
            for p in workers:
                if p.is_alive():
                    p.terminate()
            for p in workers:
                p.join(timeout=5)


class ComposeDataset(Dataset):
    """Field-wise composition: sample i = concatenated fields of every
    child dataset's sample i (reference io/dataset.py ComposeDataset)."""

    def __init__(self, datasets):
        self._ds = list(datasets)
        if not self._ds:
            raise ValueError("ComposeDataset needs at least one dataset")
        lens = {len(d) for d in self._ds}
        if len(lens) > 1:
            raise ValueError(
                f"lengths of datasets should be same, got {sorted(lens)}"
                " (reference ComposeDataset contract)")

    def __len__(self):
        return len(self._ds[0])

    def __getitem__(self, idx):
        out = []
        for d in self._ds:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list))
                       else [item])
        return tuple(out)


class SubsetRandomSampler(Sampler):
    """Random permutation over a fixed index subset (reference
    io/sampler.py SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)
        if not self.indices:
            raise ValueError("indices cannot be empty")

    def __iter__(self):
        order = np.random.permutation(len(self.indices))
        return iter([self.indices[i] for i in order])

    def __len__(self):
        return len(self.indices)


from paddle_tpu.io.prefetch import (  # noqa: E402
    DevicePrefetcher, prefetch_to_device,
)
