"""Collective communication API.

Reference: python/paddle/distributed/communication/ (all_reduce.py,
all_gather.py, all_to_all.py, reduce_scatter.py, send/recv, group.py:22)
over ProcessGroupNCCL (paddle/fluid/distributed/collective/).

TPU-native: collectives are XLA ops, not eager NCCL calls. Each Group is
bound to a mesh axis name; inside a compiled SPMD region (shard_map/pjit)
these functions lower to lax.psum / all_gather / all_to_all /
ppermute riding ICI. Outside a traced region, collectives on DistTensors
are placement transitions (reshard); on plain tensors with a size-1 group
they are identity — matching how the reference degrades on world_size=1.

Eager multi-process path: when ``jax.distributed`` is initialized across
processes (launcher / multi-host), eager collectives on plain tensors are
real: the local value becomes one shard of a global array over a
process-spanning mesh and a cached jitted ``shard_map`` collective runs
over ICI/DCN (gloo on the CPU debug backend) — the ProcessGroupNCCL role
(paddle/fluid/distributed/collective/process_group_nccl.h:37) with XLA
as the transport. P2P send/recv ride the coordination-service Store
(TCPStore role) since lone send/recv pairs are not expressible as SPMD
collectives.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.tensor import Tensor

__all__ = ["ReduceOp", "Group", "new_group", "get_group",
           "all_reduce", "all_gather", "all_gather_object", "reduce",
           "reduce_scatter", "all_to_all", "broadcast", "scatter", "barrier",
           "send", "recv", "isend", "irecv", "stream"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator: a set of ranks bound to a mesh axis name."""

    _next_gid = 0

    def __init__(self, ranks: Sequence[int], axis_name: Optional[str] = None,
                 mesh=None):
        self.ranks = list(ranks)
        self.axis_name = axis_name or f"group{Group._next_gid}"
        self.id = Group._next_gid
        Group._next_gid += 1
        self.mesh = mesh

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    world_size = nranks

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def rank(self):
        try:
            return int(lax.axis_index(self.axis_name))
        except Exception:
            pass
        try:
            me = jax.process_index()
        except Exception:
            return 0
        return self.ranks.index(me) if me in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, axis={self.axis_name}, ranks={self.ranks})"


_groups: dict = {}
_default_group: Optional[Group] = None


def new_group(ranks=None, backend=None, timeout=None, axis_name=None,
              mesh=None) -> Group:
    if ranks is None:
        # multi-process runtime: ranks are PROCESS indices (the eager
        # collective transport pairs one device per process); single
        # process: ranks are device indices (SPMD axes inside the mesh)
        try:
            nproc = jax.process_count()
        except Exception:
            nproc = 1
        ranks = list(range(nproc)) if nproc > 1 \
            else list(range(len(jax.devices())))
    g = Group(ranks, axis_name=axis_name, mesh=mesh)
    _groups[g.id] = g
    return g


def get_group(gid: int = 0) -> Optional[Group]:
    global _default_group
    if gid == 0:
        if _default_group is None:
            _default_group = new_group(axis_name="world")
        return _default_group
    return _groups.get(gid)


def _data(x):
    return x._data if isinstance(x, Tensor) else x


def _wrap_like(x, data):
    if isinstance(x, Tensor):
        out = Tensor._from_data(data, stop_gradient=x.stop_gradient)
        return out
    return data


def _in_spmd(axis_name: str) -> bool:
    """True when the axis is bound, i.e. we're inside shard_map/pmap trace."""
    try:
        lax.axis_index(axis_name)
        return True
    except (NameError, Exception):
        return False


# ---- eager cross-process transport ----------------------------------------
def _multiprocess() -> bool:
    try:
        return jax.process_count() > 1
    except Exception:
        return False


_group_meshes: dict = {}


def _group_mesh(g: "Group"):
    """(Mesh over one device per member process, my group rank, my device).

    Raises if the caller's process is not in the group — collectives are
    collective; a non-member calling one is a program bug."""
    key = tuple(g.ranks)
    me = jax.process_index()
    if me not in g.ranks:
        raise RuntimeError(
            f"process {me} is not a member of group ranks={g.ranks}")
    if key not in _group_meshes:
        import numpy as _np
        from jax.sharding import Mesh

        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        try:
            devs = [by_proc[r] for r in g.ranks]
        except KeyError as e:
            raise RuntimeError(
                f"group ranks {g.ranks} reference process {e} with no "
                f"devices (world has {jax.process_count()} processes)")
        _group_meshes[key] = Mesh(_np.array(devs), ("w",))
    mesh = _group_meshes[key]
    idx = g.ranks.index(me)
    return mesh, idx, mesh.devices[idx]


_eager_jits: dict = {}


def _eager_collective(g: "Group", kind: str, local, **static):
    """Run one cross-process collective on the local array ``local``.

    The local value is lifted to shard (group_rank) of a global array on
    the group's 1-D process mesh; a cached jitted shard_map computes the
    collective; the caller gets back its local (addressable) result."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, idx, dev = _group_mesh(g)
    n = len(g.ranks)
    local = jnp.asarray(local)
    in_sh = NamedSharding(mesh, P("w", *([None] * local.ndim)))
    shard = jax.device_put(local[None], dev)
    garr = jax.make_array_from_single_device_arrays(
        (n, *local.shape), in_sh, [shard])

    key = (tuple(g.ranks), kind, local.shape, str(local.dtype),
           tuple(sorted(static.items())))
    fn = _eager_jits.get(key)
    cold_compile = fn is None
    if fn is None:
        op = static.get("op")
        src = static.get("src", 0)
        offset = static.get("offset", 1)

        def body(x):
            v = x[0]
            if kind == "all_reduce":
                if op in (ReduceOp.SUM, "sum"):
                    return lax.psum(v, "w")
                if op in (ReduceOp.MAX, "max"):
                    return lax.pmax(v, "w")
                if op in (ReduceOp.MIN, "min"):
                    return lax.pmin(v, "w")
                if op == ReduceOp.AVG:
                    return lax.pmean(v, "w")
                return jnp.exp(lax.psum(jnp.log(v), "w"))  # prod
            if kind == "all_gather":
                return lax.all_gather(v, "w")
            if kind == "broadcast":
                i = lax.axis_index("w")
                return lax.psum(jnp.where(i == src, v,
                                          jnp.zeros_like(v)), "w")
            if kind == "reduce_scatter":
                # v: (n, chunk...) -> own reduced chunk
                if op in (ReduceOp.MAX, "max"):
                    s = lax.pmax(v, "w")
                elif op in (ReduceOp.MIN, "min"):
                    s = lax.pmin(v, "w")
                elif op == ReduceOp.AVG:
                    s = lax.pmean(v, "w")
                else:
                    s = lax.psum(v, "w")
                return s[lax.axis_index("w")][None]
            if kind == "all_to_all":
                # v: (n, chunk...) -> row j from every rank j
                out = lax.all_to_all(v[None], "w", split_axis=1,
                                     concat_axis=0)
                return out[:, 0]
            if kind == "scatter":
                i = lax.axis_index("w")
                s = lax.psum(jnp.where(i == src, v,
                                       jnp.zeros_like(v)), "w")
                return s[i][None]
            if kind == "shift":
                perm = [(i, (i + offset) % n) for i in range(n)]
                return lax.ppermute(v[None], "w", perm)
            raise ValueError(kind)

        out_spec = P("w") if kind in ("reduce_scatter", "all_to_all",
                                      "scatter", "shift") else P()
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=P("w", *([None] * local.ndim)),
            out_specs=out_spec, check_vma=False))
        _eager_jits[key] = fn
    # per-collective watchdog probe (the reference records start/end per
    # collective in comm_task_manager.cc; a hang here reports WHICH
    # collective on WHICH ranks instead of just "step timed out"). A
    # first call includes trace+XLA compile: COMPILE_ALLOWANCE deadline.
    from paddle_tpu.distributed.watchdog import (
        COMPILE_ALLOWANCE, default_watchdog,
    )

    wd = default_watchdog()
    eid = wd.arm(f"{kind}@ranks{list(g.ranks)}",
                 factor=COMPILE_ALLOWANCE if cold_compile else 1.0)
    try:
        out = fn(garr)
        res = out.addressable_data(0)
        if kind in ("reduce_scatter", "all_to_all", "scatter", "shift"):
            res = res[0] if kind in ("reduce_scatter", "scatter",
                                     "shift") else res
        return jnp.asarray(res)
    finally:
        wd.disarm(eid)


def _axis(group: Optional[Group]) -> str:
    g = group or get_group(0)
    return g.axis_name


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        d = _data(tensor)
        if op in (ReduceOp.SUM, "sum"):
            out = lax.psum(d, ax)
        elif op in (ReduceOp.MAX, "max"):
            out = lax.pmax(d, ax)
        elif op in (ReduceOp.MIN, "min"):
            out = lax.pmin(d, ax)
        elif op == ReduceOp.AVG:
            out = lax.pmean(d, ax)
        else:  # prod
            out = jnp.exp(lax.psum(jnp.log(d), ax))
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    # outside SPMD: DistTensor partial -> materialize; else identity (n=1)
    if isinstance(tensor, Tensor) and tensor.is_dist():
        from paddle_tpu.distributed.api import reshard
        from paddle_tpu.distributed.mesh import Replicate
        mesh = tensor._process_mesh
        out = reshard(tensor, mesh, [Replicate()] * mesh.ndim)
        tensor._data = out._data
        tensor._placements = out._placements
        return tensor
    if g.nranks > 1:
        if _multiprocess():
            out = _eager_collective(g, "all_reduce", _data(tensor), op=op)
            if isinstance(tensor, Tensor):
                tensor._data = out
                return tensor
            return out
        raise RuntimeError(
            "eager all_reduce across a multi-rank group requires either "
            "multiple processes (launcher + init_parallel_env) or an SPMD "
            "context (shard_map/to_static); wrap the step or use "
            "DataParallel/TrainStep which insert the reduction")
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        d = _data(tensor)
        gathered = lax.all_gather(d, ax)  # [n, ...]
        if isinstance(tensor_list, list):
            for i in range(g.nranks):
                tensor_list.append(_wrap_like(tensor, gathered[i]))
            return tensor_list
        return _wrap_like(tensor, gathered)
    if g.nranks == 1:
        if isinstance(tensor_list, list):
            tensor_list.append(tensor)
            return tensor_list
        return tensor
    if _multiprocess():
        gathered = _eager_collective(g, "all_gather", _data(tensor))
        if isinstance(tensor_list, list):
            for i in range(g.nranks):
                tensor_list.append(_wrap_like(tensor, gathered[i]))
            return tensor_list
        return _wrap_like(tensor, gathered)
    raise RuntimeError(
        "eager all_gather across a multi-rank group requires multiple "
        "processes or an SPMD context")


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)
    return object_list


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        if isinstance(tensor_list, (list, tuple)):
            stacked = jnp.stack([_data(t) for t in tensor_list])
        else:
            stacked = _data(tensor_list if tensor_list is not None
                            else tensor)
        # psum then take own chunk == reduce-scatter (XLA fuses this)
        summed = lax.psum(stacked, ax)
        idx = lax.axis_index(ax)
        out = summed[idx] if summed.shape[0] == g.nranks else \
            lax.dynamic_slice_in_dim(summed, idx * (summed.shape[0] //
                                                    g.nranks),
                                     summed.shape[0] // g.nranks, 0)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks == 1:
        src = tensor_list[0] if isinstance(tensor_list, (list, tuple)) \
            else (tensor_list if tensor_list is not None else tensor)
        if isinstance(tensor, Tensor):
            tensor._data = _data(src)
            return tensor
        return src
    if _multiprocess():
        if isinstance(tensor_list, (list, tuple)):
            stacked = jnp.stack([_data(t) for t in tensor_list])
        else:
            stacked = _data(tensor_list if tensor_list is not None
                            else tensor)
        out = _eager_collective(g, "reduce_scatter", stacked, op=op)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    raise RuntimeError(
        "eager reduce_scatter across a multi-rank group requires multiple "
        "processes or an SPMD context")


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        if isinstance(in_tensor_list, (list, tuple)):
            stacked = jnp.stack([_data(t) for t in in_tensor_list])
        else:
            stacked = _data(in_tensor_list)
        out = lax.all_to_all(stacked, ax, split_axis=0, concat_axis=0,
                             tiled=False)
        if isinstance(out_tensor_list, list):
            for i in range(g.nranks):
                out_tensor_list.append(_wrap_like(
                    in_tensor_list[0] if isinstance(in_tensor_list,
                                                    (list, tuple))
                    else in_tensor_list, out[i]))
            return out_tensor_list
        return out
    if g.nranks == 1:
        if isinstance(out_tensor_list, list):
            out_tensor_list.extend(in_tensor_list)
            return out_tensor_list
        return in_tensor_list
    if _multiprocess():
        if isinstance(in_tensor_list, (list, tuple)):
            stacked = jnp.stack([_data(t) for t in in_tensor_list])
        else:
            stacked = _data(in_tensor_list)
        out = _eager_collective(g, "all_to_all", stacked)
        if isinstance(out_tensor_list, list):
            ref = in_tensor_list[0] if isinstance(in_tensor_list,
                                                  (list, tuple)) \
                else in_tensor_list
            for i in range(g.nranks):
                out_tensor_list.append(_wrap_like(ref, out[i]))
            return out_tensor_list
        return out
    raise RuntimeError(
        "eager all_to_all across a multi-rank group requires multiple "
        "processes or an SPMD context")


def broadcast(tensor, src=0, group=None, sync_op=True):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        d = _data(tensor)
        if src not in g.ranks:
            raise ValueError(
                f"src rank {src} is not a member of group ranks="
                f"{g.ranks}")
        src_local = g.get_group_rank(src)
        # select src's value on every rank: mask + psum
        idx = lax.axis_index(ax)
        masked = jnp.where(idx == src_local, d, jnp.zeros_like(d))
        out = lax.psum(masked, ax)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks == 1:
        return tensor
    if _multiprocess():
        if src not in g.ranks:
            raise ValueError(
                f"src rank {src} is not a member of group ranks="
                f"{g.ranks}")
        src_local = g.get_group_rank(src)
        out = _eager_collective(g, "broadcast", _data(tensor),
                                src=src_local)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    raise RuntimeError(
        "eager broadcast across a multi-rank group requires multiple "
        "processes or an SPMD context")


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = group or get_group(0)
    ax = g.axis_name
    if _in_spmd(ax):
        stacked = jnp.stack([_data(t) for t in tensor_list]) \
            if isinstance(tensor_list, (list, tuple)) else _data(tensor_list)
        stacked = broadcast(stacked, src=src, group=g)
        idx = lax.axis_index(ax)
        out = stacked[idx]
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    if g.nranks == 1:
        src_t = tensor_list[0] if tensor_list else tensor
        if isinstance(tensor, Tensor):
            tensor._data = _data(src_t)
            return tensor
        return src_t
    if _multiprocess():
        if src not in g.ranks:
            raise ValueError(
                f"src rank {src} is not a member of group ranks="
                f"{g.ranks}")
        src_local = g.get_group_rank(src)
        # only src's tensor_list matters; other ranks contribute zeros
        if tensor_list:
            stacked = jnp.stack([_data(t) for t in tensor_list])
        else:
            d = _data(tensor)
            stacked = jnp.zeros((g.nranks, *d.shape), d.dtype)
        out = _eager_collective(g, "scatter", stacked, src=src_local)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    raise RuntimeError(
        "eager scatter across a multi-rank group requires multiple "
        "processes or an SPMD context")


_p2p_seq: dict = {}


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P send. Inside a compiled schedule p2p is a ppermute (see
    ``shift`` and distributed/fleet/pp.py). Eagerly across processes it
    rides the coordination-service Store (TCPStore role) — correct but
    control-plane speed; bulk pipelines should use the compiled path."""
    if _multiprocess():
        from paddle_tpu.distributed.store import current_store

        me = jax.process_index()
        seq = _p2p_seq[(me, dst)] = _p2p_seq.get((me, dst), 0) + 1
        d = _data(tensor)
        import numpy as _np

        arr = _np.asarray(d)
        # '\n' separator: dtype.str may itself start with '|' (bool/int8)
        meta = f"{arr.dtype.str}\n{','.join(map(str, arr.shape))}\n"
        current_store().set(f"p2p/{me}->{dst}/{seq}",
                            meta.encode() + arr.tobytes())
        return tensor
    raise RuntimeError(
        "bare send/recv need a multi-process runtime; in compiled SPMD "
        "use p2p helpers (paddle_tpu.distributed.fleet.pp) or "
        "batch_isend_irecv")


def recv(tensor, src=0, group=None, sync_op=True):
    if _multiprocess():
        from paddle_tpu.distributed.store import current_store

        me = jax.process_index()
        seq = _p2p_seq[("r", src, me)] = \
            _p2p_seq.get(("r", src, me), 0) + 1
        raw = current_store().get(f"p2p/{src}->{me}/{seq}")
        import numpy as _np

        dts, shs, payload = raw.split(b"\n", 2)
        shape = tuple(int(x) for x in shs.decode().split(",") if x)
        arr = _np.frombuffer(payload, dtype=_np.dtype(
            dts.decode())).reshape(shape)
        out = jnp.asarray(arr)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return out
    raise RuntimeError(
        "bare send/recv need a multi-process runtime; in compiled SPMD "
        "use p2p helpers (paddle_tpu.distributed.fleet.pp) or "
        "batch_isend_irecv")


isend = send
irecv = recv


def barrier(group=None):
    if _multiprocess():
        from paddle_tpu.distributed.store import current_store

        g = group or get_group(0)
        store = current_store()
        if hasattr(store, "_c"):
            # subgroup barriers wait only on member processes
            pids = None if len(g.ranks) >= jax.process_count() \
                else list(g.ranks)
            store.barrier(
                f"comm{g.id}-{_p2p_seq.setdefault(('b', g.id), 0)}",
                process_ids=pids)
            _p2p_seq[("b", g.id)] += 1
            return
    jax.block_until_ready(jnp.zeros(()))


# ---- ppermute-based shift helpers (the TPU p2p idiom) ----------------------
def shift(x, group: Group, offset: int = 1):
    """Rotate values around the group ring by ``offset``. Inside SPMD this
    is the collective_permute that replaces NCCL send/recv for
    pipeline/ring algorithms; eagerly across processes it runs as a
    jitted shard_map ppermute."""
    ax = group.axis_name
    n = group.nranks
    if not _in_spmd(ax) and _multiprocess() and n > 1:
        return _eager_collective(group, "shift", _data(x), offset=offset)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(_data(x), ax, perm)


class stream:
    """paddle.distributed.stream.* parity — on TPU there are no user-visible
    streams; these forward to the plain collectives."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    all_to_all = staticmethod(all_to_all)
    broadcast = staticmethod(broadcast)
    scatter = staticmethod(scatter)
