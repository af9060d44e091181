"""Hybrid-parallel training engine (GSPMD path).

Reference analog: the semi-auto static Engine
(python/paddle/distributed/auto_parallel/static/engine.py:62) plus the
dygraph hybrid wrappers (fleet/meta_parallel/). There the flow is
trace → complete dist attrs → partition program → insert reshards →
executor. Here the whole flow is: annotate param/activation shardings →
jit the (forward+backward+optimizer) step with in/out shardings → XLA's
GSPMD partitioner completes the sharding propagation (the role of
completion.py + SPMD rules) and inserts collectives (the role of
reshard.py), compiled once onto the mesh.

ZeRO mapping (reference: DygraphShardingOptimizer stage1/2,
GroupShardedStage3):
  stage 0: params+slots follow placement hints (TP) only
  stage 1/2: optimizer slots additionally sharded over the dp axis
  stage 3: parameters themselves sharded over dp (XLA all-gathers
           just-in-time per layer = the broadcast-on-use of stage 3)
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core import generator as gen
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import ProcessMesh, Replicate, Shard
from paddle_tpu.jit.trace import functionalize
from paddle_tpu.ops.pallas.common import kernel_mesh

__all__ = ["current_mesh", "set_current_mesh", "shard_model_parameters",
           "ParallelTrainStep", "ParallelConfig"]

_current_mesh: Optional[ProcessMesh] = None


def current_mesh() -> Optional[ProcessMesh]:
    return _current_mesh


def set_current_mesh(mesh: Optional[ProcessMesh]):
    global _current_mesh
    _current_mesh = mesh


class ParallelConfig:
    """Which mesh axes mean what + ZeRO stage + batch placement."""

    def __init__(self, dp_axes: Sequence[str] = ("dp",),
                 sharding_stage: int = 0,
                 sharding_axis: str = "dp",
                 batch_dim: int = 0,
                 remat: bool = False):
        self.dp_axes = tuple(dp_axes)
        self.sharding_stage = sharding_stage
        self.sharding_axis = sharding_axis
        self.batch_dim = batch_dim
        self.remat = remat


def _pspec_from_hints(p, mesh: ProcessMesh, extra_axis=None, offset=0,
                      lead=None) -> PartitionSpec:
    """placement hints {axis_name: Shard(dim)} -> PartitionSpec; optionally
    add ``extra_axis`` sharding on the first divisible dim (ZeRO-3).
    ``offset`` shifts hint dims right (for stacked leading axes) and
    ``lead`` names the mesh axis sharding dim 0 (pipeline stacking)."""
    ndim = (p._data.ndim if isinstance(p, Tensor) else p.ndim) + offset
    spec: List = [None] * ndim
    if lead is not None:
        spec[0] = lead
    hints: Dict = getattr(p, "_placement_hints", None) or {}
    used = set()
    base_ndim = ndim - offset
    for ax_name, pl in hints.items():
        if ax_name not in mesh.dim_names or not isinstance(pl, Shard):
            continue
        d = (pl.dim % base_ndim if base_ndim else 0) + offset
        if spec[d] is None:
            spec[d] = ax_name
        elif isinstance(spec[d], tuple):
            spec[d] += (ax_name,)
        else:
            spec[d] = (spec[d], ax_name)
        used.add(ax_name)
    if extra_axis and extra_axis in mesh.dim_names and \
            extra_axis not in used and base_ndim > 0:
        n = mesh.get_dim_size(extra_axis)
        shape = p._data.shape if isinstance(p, Tensor) else p.shape
        for d in range(base_ndim):
            if spec[d + offset] is None and shape[d] % n == 0:
                spec[d + offset] = extra_axis
                break
    return PartitionSpec(*spec)


def mesh_dim_product(mesh, entry):
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        out = 1
        for e in entry:
            out *= mesh.get_dim_size(e)
        return out
    return mesh.get_dim_size(entry)


def shard_model_parameters(model, mesh: ProcessMesh,
                           config: Optional[ParallelConfig] = None):
    """Eagerly device_put every param/buffer onto the mesh per its hints
    (+ZeRO-3 param sharding), so HBM is spread before the first step."""
    config = config or ParallelConfig()
    jmesh = mesh.jax_mesh()
    extra = config.sharding_axis if config.sharding_stage >= 3 else None
    for p in model.parameters():
        spec = _pspec_from_hints(p, mesh, extra_axis=extra)
        p._data = jax.device_put(p._data, NamedSharding(jmesh, spec))
        p._process_mesh = mesh
    for _, b in model.named_buffers():
        b._data = jax.device_put(
            b._data, NamedSharding(jmesh, PartitionSpec()))
    return model


class ParallelTrainStep:
    """Whole-step compiled hybrid-parallel training over a mesh.

    Same contract as jit.TrainStep (shares the functionalizer and the
    optimizer's pure rule) with sharding: batch sharded over dp axes,
    params/slots per hints + ZeRO stage, buffer donation for in-place HBM
    updates.
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 mesh: ProcessMesh, config: Optional[ParallelConfig] = None,
                 n_model_inputs: int = 1, scaler=None,
                 skip_nonfinite: bool = False):
        from paddle_tpu import amp as _amp

        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._mesh = mesh
        self._config = config or ParallelConfig()
        self._n_inputs = n_model_inputs
        self._scaler = scaler if scaler is not None and scaler.is_enable() \
            else None
        self._scaler_state = _amp.scaler_init_state(scaler)
        # in-graph NaN/Inf guard, same contract as
        # jit.TrainStep(skip_nonfinite=True): a non-finite loss or grad
        # makes the step an identity update (params/slots/buffers/step
        # bit-identical; only the RNG chain advances), counted on device
        # and surfaced via ``skipped_steps`` / profiler.counters()
        self._skip_nonfinite = bool(skip_nonfinite)
        cfg = self._config

        shard_model_parameters(model, mesh, cfg)
        self._apply, (self._pnames, self._params), \
            (self._bnames, self._buffers) = functionalize(model)
        if optimizer._parameter_list is None:
            optimizer._parameter_list = list(self._params)

        jmesh = mesh.jax_mesh()
        extra3 = cfg.sharding_axis if cfg.sharding_stage >= 3 else None
        extra12 = cfg.sharding_axis if cfg.sharding_stage >= 1 else None
        self._param_sh = [
            NamedSharding(jmesh, _pspec_from_hints(p, mesh,
                                                   extra_axis=extra3))
            for p in self._params]
        # slots: shard over dp for any ZeRO stage >= 1
        self._slot_sh = [
            NamedSharding(jmesh, _pspec_from_hints(
                p, mesh, extra_axis=extra12 or extra3))
            for p in self._params]
        repl = NamedSharding(jmesh, PartitionSpec())
        self._repl = repl

        # init optimizer slots, placed at their slot shardings
        self._slots = []
        for p, sh in zip(self._params, self._slot_sh):
            s = optimizer._slots.get(id(p))
            if s is None:
                s = optimizer._init_slots_mp(p._data)
            s = {k: jax.device_put(v, sh) for k, v in s.items()}
            optimizer._slots[id(p)] = s
            self._slots.append(s)
        self._trainable = [not p.stop_gradient for p in self._params]

        batch_axes = tuple(a for a in cfg.dp_axes if a in mesh.dim_names)
        if cfg.sharding_axis in mesh.dim_names and cfg.sharding_stage >= 1 \
                and cfg.sharding_axis not in batch_axes:
            batch_axes = batch_axes + (cfg.sharding_axis,)
        self._batch_axes = batch_axes

        def batch_sharding(ndim):
            spec = [None] * ndim
            if batch_axes and ndim > cfg.batch_dim:
                spec[cfg.batch_dim] = batch_axes if len(batch_axes) > 1 \
                    else batch_axes[0]
            return NamedSharding(jmesh, PartitionSpec(*spec))

        self._batch_sharding = batch_sharding

        def step_fn(carry, param_datas, slot_list, buffer_datas, lr,
                    scaler_state, *batch):
            set_current_mesh(mesh)
            # device-carried (step, rng chain, nonfinite-skip count) —
            # committed-args fast path, no per-step host scalar
            # transfer (see jit/train.py)
            step, chain, nskip = carry
            step = step + 1.0
            chain, key = jax.random.split(chain)
            scaling = scaler_state is not None

            def loss_of(trainable_params):
                full = list(param_datas)
                it = iter(trainable_params)
                for i, t in enumerate(self._trainable):
                    if t:
                        full[i] = next(it)
                apply_fn = self._apply
                if cfg.remat:
                    apply_fn = jax.checkpoint(
                        lambda pd, bd, k, *ins: self._apply(pd, bd, k, *ins),
                        static_argnums=())
                out, new_buf = apply_fn(full, buffer_datas, key,
                                        *batch[: self._n_inputs])
                outs = out if isinstance(out, tuple) else (out,)
                ins = [Tensor._from_data(o) for o in outs]
                labels = [Tensor._from_data(b)
                          for b in batch[self._n_inputs:]]
                loss = self._loss_fn(*(ins + labels))
                ld = loss._data if isinstance(loss, Tensor) else loss
                if ld.ndim > 0:
                    ld = jnp.mean(ld)
                scaled = ld * scaler_state[0] if scaling else ld
                return scaled, (ld, new_buf)

            trainable_params = [p for p, t in zip(param_datas,
                                                  self._trainable) if t]
            # a Pallas kernel in the model (flash attention) cannot be
            # partitioned by GSPMD: declare how attention operands are
            # sharded here, so it runs per shard under shard_map
            with kernel_mesh(
                    mesh.jax_mesh(),
                    heads="mp" if "mp" in mesh.dim_names else None,
                    batch=batch_axes or None):
                (_, (loss, new_buffers)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(trainable_params)

            found_inf = None
            new_scaler_state = scaler_state
            if scaling:
                from paddle_tpu import amp as _amp

                grads, found_inf = _amp.scaler_unscale_and_check(
                    list(grads), scaler_state)
                new_scaler_state = _amp.scaler_update_state(
                    self._scaler, scaler_state, found_inf)

            nonfinite = None
            if self._skip_nonfinite:
                from paddle_tpu.jit.train import nonfinite_any

                nonfinite = nonfinite_any(loss, grads)

            clip_fn = getattr(optimizer._grad_clip, "clip_fn", None)
            if clip_fn is not None:
                grads = clip_fn(list(grads))

            skip = found_inf
            if nonfinite is not None:
                skip = nonfinite if skip is None else (skip | nonfinite)

            new_params = list(param_datas)
            new_slots = list(slot_list)
            gi = 0
            for i, t in enumerate(self._trainable):
                if not t:
                    continue
                g = grads[gi]
                gi += 1
                optimizer._current_decay_enabled = optimizer._decay_enabled(
                    self._params[i])
                optimizer._current_mask = \
                    optimizer._param_masks.get(id(self._params[i]))
                np_, ns = optimizer._rule_mp(param_datas[i], g,
                                             slot_list[i], lr, step)
                optimizer._current_decay_enabled = True
                optimizer._current_mask = None
                if skip is not None:
                    np_ = jnp.where(skip, param_datas[i], np_)
                    ns = {k: jnp.where(skip, slot_list[i][k], v)
                          for k, v in ns.items()}
                new_params[i] = np_
                new_slots[i] = ns
            if nonfinite is not None:
                # identity update: buffers and the step counter roll
                # back too (the scaler state must NOT — the dynamic
                # loss-scale schedule has to see its overflow)
                nskip = nskip + jnp.where(nonfinite, 1.0, 0.0)
                keep = ~nonfinite
                new_buffers = [jnp.where(keep, nb, ob) for nb, ob in
                               zip(new_buffers, buffer_datas)]
                step = jnp.where(keep, step, step - 1.0)
            set_current_mesh(None)
            return loss, (step, chain, nskip), new_params, new_slots, \
                new_buffers, new_scaler_state

        self._step_fn = step_fn
        self._jitted = None  # built lazily at first call (needs batch avals)
        # step seeds from the optimizer counter so checkpoint resume keeps
        # bias correction right (see jit/train.py _sync_step_carry)
        # placed like the carry every step returns: an uncommitted
        # first carry makes step 2 a second compile of the whole step
        # (see jit.TrainStep._commit_state)
        self._carry = tuple(jax.device_put(c, repl) for c in (
            jnp.asarray(float(optimizer._step_count), jnp.float32),
            gen.default_generator.next_key(),
            jnp.zeros((), jnp.float32)))  # nonfinite skips
        if self._scaler_state is not None:
            self._scaler_state = tuple(jax.device_put(v, repl)
                                       for v in self._scaler_state)
        self._host_step_mirror = optimizer._step_count
        if self._skip_nonfinite:
            from paddle_tpu.jit.train import install_nonfinite_observability

            install_nonfinite_observability(self, optimizer)
        self._lr_val = None
        self._lr_arr = None
        self._wd_warm = None  # last batch shapes (compile detection)

    @property
    def skipped_steps(self) -> int:
        """Steps the ``skip_nonfinite`` guard turned into identity
        updates. Carried on device (no per-step sync); reading blocks
        on the last dispatched step."""
        return int(np.asarray(self._carry[2]))

    def _build_jit(self, batch_datas):
        scaler_sh = self._repl if self._scaler_state is not None else None
        carry_sh = (self._repl, self._repl, self._repl)
        in_shardings = (
            carry_sh,
            self._param_sh,
            [{k: self._slot_sh[i] for k in s} for i, s in
             enumerate(self._slots)],
            [self._repl] * len(self._buffers),
            self._repl,
            scaler_sh,
            *[self._batch_sharding(b.ndim) for b in batch_datas],
        )
        out_shardings = (
            self._repl,  # loss
            carry_sh,
            self._param_sh,
            [{k: self._slot_sh[i] for k in s} for i, s in
             enumerate(self._slots)],
            [self._repl] * len(self._buffers),
            scaler_sh,
        )
        self._jitted = jax.jit(self._step_fn,
                               in_shardings=in_shardings,
                               out_shardings=out_shardings,
                               donate_argnums=(0, 1, 2, 3))

    def _place_batch(self, batch):
        return tuple(
            jax.device_put(
                b._data if isinstance(b, Tensor) else jnp.asarray(b),
                self._batch_sharding(
                    (b._data if isinstance(b, Tensor)
                     else jnp.asarray(b)).ndim))
            for b in batch)

    def __call__(self, *batch):
        datas = self._place_batch(batch)
        if self._jitted is None:
            self._build_jit(datas)
        if self._opt._step_count != self._host_step_mirror:
            # optimizer counter changed externally (checkpoint resume)
            self._carry = (jnp.asarray(float(self._opt._step_count),
                                       jnp.float32), self._carry[1],
                           self._carry[2])
        self._opt._step_count += 1  # host mirror (schedulers, state_dict)
        self._host_step_mirror = self._opt._step_count
        lr_val = float(self._opt.get_lr())
        if self._lr_arr is None or lr_val != self._lr_val:
            self._lr_val = lr_val
            self._lr_arr = jax.device_put(np.float32(lr_val), self._repl)
        param_datas = [p._data for p in self._params]
        buffer_datas = [b._data for b in self._buffers]
        from paddle_tpu.distributed.watchdog import (
            arm_step, attach_step, default_watchdog,
        )

        # new batch shapes force a retrace: stretched (compile) deadline
        shapes = tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        wd_id = arm_step(f"ParallelTrainStep#{self._opt._step_count}",
                         cold=self._wd_warm != shapes)
        set_current_mesh(self._mesh)
        try:
            loss, self._carry, new_params, new_slots, new_buffers, \
                new_scaler_state = self._jitted(
                    self._carry, param_datas, self._slots, buffer_datas,
                    self._lr_arr, self._scaler_state, *datas)
        except BaseException:
            default_watchdog().disarm(wd_id)
            raise
        finally:
            set_current_mesh(None)
        self._wd_warm = shapes
        attach_step(wd_id, loss)
        for p, np_ in zip(self._params, new_params):
            p._data = np_
        for b, nb in zip(self._buffers, new_buffers):
            b._data = nb
        self._slots = new_slots
        for p, s in zip(self._params, new_slots):
            self._opt._slots[id(p)] = s
        if new_scaler_state is not None:
            from paddle_tpu import amp as _amp

            self._scaler_state = new_scaler_state
            _amp.scaler_sync_from_state(self._scaler, new_scaler_state)
        return Tensor._from_data(loss)
