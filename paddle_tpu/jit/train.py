"""TrainStep: whole-step compilation.

The reference reaches peak throughput via static graph + CINN fusion
(SURVEY.md §3.3); the TPU-native equivalent is compiling the entire
(forward + backward + optimizer) step into one XLA executable. TrainStep
reuses: the Layer's functionalized apply (jit/trace.py), the optimizer's
pure ``_rule`` (optimizer/optimizer.py), and ClipGradByGlobalNorm's pure
``clip_fn`` — so eager and compiled training are numerically identical.

Buffer donation on params + optimizer slots gives in-place updates in HBM
(the role of the reference's buffer reuse / inplace pass).

Dispatch design: every per-step argument must be a *committed device
array* so each call takes jax's C++ fast dispatch path. Host-constructed
scalars (``jnp.asarray`` of a python float) force the python slow path
on every call of a several-hundred-argument step. Therefore the step
counter and the RNG key are *carried on
device* inside the donated state (incremented / split inside the jitted
step), and the learning rate is a cached committed array that is only
re-transferred when the host-side scheduler actually changes its value.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import generator as gen
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.trace import functionalize
from paddle_tpu.nn.clip import ClipGradByGlobalNorm
from paddle_tpu.profiler import StepProgram, span

__all__ = ["TrainStep"]


def nonfinite_any(loss, grads):
    """In-graph reduction the ``skip_nonfinite`` guard gates on: True
    when the loss or ANY gradient holds a NaN/Inf. Shared by TrainStep,
    ParallelTrainStep and PipelineTrainStep so the guard semantics
    (checked after unscaling — scaled-inf vs true inf — and BEFORE
    clipping, where a global-norm clip of a NaN grad would smear it
    into NaN-everywhere) live in one place."""
    nf = jnp.any(~jnp.isfinite(loss))
    for g in grads:
        nf = nf | jnp.any(~jnp.isfinite(g))
    return nf


def install_nonfinite_observability(step, optimizer) -> str:
    """Wire a ``skip_nonfinite`` train step into the observability and
    checkpoint machinery (shared by TrainStep, ParallelTrainStep and
    PipelineTrainStep — one place to fix, three engines):

    * a ``train_step/nonfinite_skipped#<id>`` counter provider over the
      step's ``skipped_steps`` (weakref'd: counters() drops it when the
      step dies, and a finalizer unregisters it even if counters() is
      never read — no per-instance leak);
    * ``optimizer._applied_step_provider`` returning the device-APPLIED
      step from the carry (a skipped step rolls the device counter
      back, and a checkpoint restore must not jump bias-corrected
      rules ahead by the skips).

    Returns the counter name."""
    import weakref

    from paddle_tpu import profiler as _prof

    ref = weakref.ref(step)
    cname = f"train_step/nonfinite_skipped#{id(step)}"
    _prof.register_counter_provider(
        cname, lambda: (None if ref() is None else ref().skipped_steps))
    weakref.finalize(step, _prof.unregister_counter_provider, cname)
    optimizer._applied_step_provider = (
        lambda: (None if ref() is None
                 else int(np.asarray(ref()._carry[0]))))
    return cname


class TrainStep:
    """``donate=True`` (default) hands params/optimizer slots/buffers to
    XLA as donated inputs: the compiled step updates state in place in
    HBM instead of allocating fresh buffers and copying — the single
    biggest lever on the profiler's ``copy_frac`` metric. The cost is
    that an array snapshotted BEFORE a step (e.g. ``p._data`` stashed in
    user code) is dead after it; TrainStep itself rebinds every carried
    reference (params, buffers, ``optimizer._slots``) after each
    dispatch. ``donate=False`` opts out — the equality tests in
    tests/test_train_donation.py pin the two modes to bit-identical
    numerics."""

    def __init__(self, model, loss_fn: Callable, optimizer,
                 accumulate_steps: int = 1, sharding=None, scaler=None,
                 donate: bool = True, skip_nonfinite: bool = False):
        from paddle_tpu import amp as _amp

        self._donate = bool(donate)
        # in-graph robustness guard: a NaN/Inf loss or grad turns the
        # step into the identity update (params, slots, buffers and the
        # step counter bit-identical to before; only the RNG chain
        # advances) instead of poisoning the whole run — the compiled
        # analog of the reference's FLAGS_check_nan_inf + skip. Skips
        # are counted on device (no per-step host sync) and surfaced via
        # ``skipped_steps`` / profiler.counters().
        self._skip_nonfinite = bool(skip_nonfinite)
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._scaler = scaler if scaler is not None and scaler.is_enable() \
            else None
        self._scaler_state = _amp.scaler_init_state(scaler)
        self._apply, (self._pnames, self._params), \
            (self._bnames, self._buffers) = functionalize(model)
        if optimizer._parameter_list is None:
            optimizer._parameter_list = list(self._params)
        # init optimizer slots eagerly so they are part of the carried state
        self._slots = []
        for p in self._params:
            s = optimizer._slots.get(id(p))
            if s is None:
                s = optimizer._init_slots_mp(p._data)
                optimizer._slots[id(p)] = s
            self._slots.append(s)
        self._trainable = [not p.stop_gradient for p in self._params]
        self._sharding = sharding

        def make_step_fn(outcomes):
            """Build the whole-step function; when ``outcomes`` is a
            recorded SOT guard path (jit/sot.py), the model trace replays
            it and the state update is gated on the guards still holding,
            so a mis-specialized run is a no-op that can be retried."""
            from paddle_tpu.jit import sot as _sot

            def step_fn(n_inputs, carry, param_datas, slot_list,
                        buffer_datas, lr, scaler_state, *batch):
                # (step, key, nonfinite-skip count) live on device: no
                # per-step host transfer
                step, chain, nskip = carry
                with jax.named_scope("optimizer"):  # the step's carry
                    step = step + 1.0
                    chain, key = jax.random.split(chain)
                scaling = scaler_state is not None

                def loss_of(trainable_params):
                    full = _merge(param_datas, trainable_params,
                                  self._trainable)
                    if outcomes is None:
                        out, new_buf = self._apply(full, buffer_datas, key,
                                                   *batch[:n_inputs])
                        guard_arr = jnp.zeros((0,), jnp.float32)
                    else:
                        rec = _sot.GuardRecorder("replay", outcomes)
                        with _sot.use(rec):
                            out, new_buf = self._apply(
                                full, buffer_datas, key,
                                *batch[:n_inputs])
                        guard_arr = _sot.guard_values(rec)
                    outs = out if isinstance(out, tuple) else (out,)
                    ins = [Tensor._from_data(o) for o in outs]
                    loss = self._compute_loss(ins, batch, n_inputs)
                    ld = loss._data if isinstance(loss, Tensor) else loss
                    # loss scaling happens BEFORE backward (fp16 underflow)
                    scaled = ld * scaler_state[0] if scaling else ld
                    return scaled, (ld, new_buf, guard_arr)

                trainable_params = [p for p, t in zip(param_datas,
                                                      self._trainable) if t]
                (_, (loss, new_buffers, guard_arr)), grads = \
                    jax.value_and_grad(loss_of, has_aux=True)(
                        trainable_params)
                valid = _sot.guards_match_traced(guard_arr, outcomes or ())

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
                    nonfinite = nonfinite_any(loss, grads)

                clip = optimizer._grad_clip
                clip_fn = getattr(clip, "clip_fn", None)
                if clip_fn is not None:
                    with jax.named_scope("optimizer"):
                        grads = clip_fn(list(grads))

                skip = None
                if found_inf is not None:
                    # skip update on overflow (reference GradScaler.step)
                    skip = found_inf
                if nonfinite is not None:
                    skip = nonfinite if skip is None else (skip | nonfinite)
                if outcomes:
                    inval = ~valid
                    skip = inval if skip is None else (skip | inval)

                new_params = list(param_datas)
                new_slots = list(slot_list)
                gi = 0
                for i, t in enumerate(self._trainable):
                    if not t:
                        continue
                    g = grads[gi]
                    gi += 1
                    # per-param decay exclusion + ASP mask are
                    # trace-time static
                    optimizer._current_decay_enabled = \
                        optimizer._decay_enabled(self._params[i])
                    optimizer._current_mask = \
                        optimizer._param_masks.get(id(self._params[i]))
                    with jax.named_scope("optimizer"):
                        np_, ns = optimizer._rule_mp(
                            param_datas[i], g, slot_list[i], lr, step)
                        if skip is not None:
                            np_ = jnp.where(skip, param_datas[i], np_)
                            ns = {k: jnp.where(skip, slot_list[i][k], v)
                                  for k, v in ns.items()}
                    optimizer._current_decay_enabled = True
                    optimizer._current_mask = None
                    new_params[i] = np_
                    new_slots[i] = ns
                # a skipped/invalid run must leave carried state
                # untouched (the rng chain still advances — a skipped
                # draw is benign)
                rollback = None
                if nonfinite is not None:
                    rollback = nonfinite
                    # a guard-miss run is discarded and replayed, so only
                    # the valid run counts its skip (no double count)
                    nskip = nskip + jnp.where(nonfinite & valid, 1.0, 0.0)
                if outcomes:
                    inval = ~valid
                    rollback = inval if rollback is None \
                        else (rollback | inval)
                    # only a guard miss rolls the scaler back (the step
                    # will be replayed); a nonfinite skip must NOT — the
                    # dynamic loss-scale schedule has to see the overflow
                    if new_scaler_state is not None:
                        new_scaler_state = tuple(
                            jnp.where(valid, nv, ov) for nv, ov in
                            zip(new_scaler_state, scaler_state))
                if rollback is not None:
                    keep = ~rollback
                    new_buffers = [jnp.where(keep, nb, ob) for nb, ob in
                                   zip(new_buffers, buffer_datas)]
                    step = jnp.where(keep, step, step - 1.0)
                return loss, (step, chain, nskip), new_params, \
                    new_slots, new_buffers, new_scaler_state, valid

            return step_fn

        self._make_raw = make_step_fn  # un-jitted body (run_steps scans it)

        def make_jitted(outcomes):
            # n_inputs is a static jit arg: calling with a different
            # n_model_inputs retraces instead of reusing a stale split
            return jax.jit(make_step_fn(outcomes), static_argnums=(0,),
                           donate_argnums=self._donate_argnums())

        self._make_jitted = make_jitted
        self._jitted = make_jitted(None)  # optimistic whole-graph path
        # the step as profiler.program_regions() knows it
        self._step_program = StepProgram("train.step", self._jitted)
        self._multi_jitted = {}  # (k, stacked) -> scanned executable
        from paddle_tpu.jit.sot import PathCache

        self._sot_cache: Optional[PathCache] = None  # built on graph break
        # device-carried (step, rng chain); the chain is seeded ONCE from
        # the global generator (static-graph semantics: the reference bakes
        # seeds at program build) and split on-device each step. The step
        # seeds from the optimizer's counter so checkpoint resume keeps
        # Adam-style bias correction right (see _sync_step_carry).
        self._carry = (jnp.asarray(float(optimizer._step_count),
                                   jnp.float32),
                       gen.default_generator.next_key(),
                       jnp.zeros((), jnp.float32))  # nonfinite skips
        self._host_step_mirror = optimizer._step_count
        if self._skip_nonfinite:
            install_nonfinite_observability(self, optimizer)
        self._lr_val = None
        self._lr_arr = None
        self._wd_warm: dict = {}  # id(jitted) -> last batch shapes
        self._dispatch_failed = False  # arms the re-dispatch guard
        self._commit_state()

    def _commit_state(self):
        """Freshly initialized params/slots/buffers/carry are UNCOMMITTED
        arrays, while everything a step returns is committed: left alone,
        step 2 presents the jit with different argument shardings than
        step 1 and the whole step compiles a second time. Committing the
        carried state once, here, makes step 1's program the only one."""
        for t in list(self._params) + list(self._buffers):
            t._data = self._commit(t._data)
        self._slots = [{k: self._commit(v) for k, v in s.items()}
                       for s in self._slots]
        for p, s in zip(self._params, self._slots):
            self._opt._slots[id(p)] = s
        self._carry = tuple(self._commit(c) for c in self._carry)
        if self._scaler_state is not None:
            self._scaler_state = tuple(self._commit(v)
                                       for v in self._scaler_state)

    def _donate_argnums(self):
        """(carry, params, slots, buffers) when donating, () otherwise.
        Batch args, the LR and the scaler state are never donated: the
        LR array is host-cached across steps and batches may be reused
        (steady-state benchmarking, run_steps unstacked)."""
        return (1, 2, 3, 4) if self._donate else ()

    def _state_arrays(self):
        """Every device array the compiled step donates (the arrays a
        failed dispatch could have consumed)."""
        for c in self._carry:
            yield "carry", c
        for p in self._params:
            yield "param", p._data
        for b in self._buffers:
            yield "buffer", b._data
        for s in self._slots:
            for k, v in s.items():
                yield f"slot:{k}", v

    def _dead_donated_state(self):
        if not self._donate:
            return []
        return sorted({kind for kind, a in self._state_arrays()
                       if getattr(a, "is_deleted", lambda: False)()})

    def _check_donated_state(self, context: str):
        """Donation guard for retrace/guard-miss paths: a dispatch that
        failed BEFORE execution (trace error -> SOT switch, shape
        retrace) leaves the donated buffers alive and the step can simply
        be re-run; a dispatch that failed AFTER consuming them cannot be
        — fail loudly instead of letting the next eager op hit a deleted
        PJRT buffer."""
        dead = self._dead_donated_state()
        if dead:
            raise RuntimeError(
                f"TrainStep state was donated to a dispatch that failed "
                f"after consuming it ({context}: {dead} buffers "
                f"deleted). The in-place update was lost; restore from "
                f"a checkpoint, or construct the TrainStep with "
                f"donate=False to trade copy overhead for re-runnable "
                f"failures.")

    def _warn_donated_state(self, context: str):
        """Same detection, but on a path that must re-raise the ORIGINAL
        failure (e.g. the nan/inf checker's FloatingPointError) — the
        state-loss note must not mask it."""
        dead = self._dead_donated_state()
        if dead:
            import warnings

            warnings.warn(
                f"TrainStep: the failed dispatch ({context}) had already "
                f"consumed the donated state ({dead}); the step cannot "
                f"be retried — restore from a checkpoint or use "
                f"donate=False", RuntimeWarning, stacklevel=3)

    def _sync_step_carry(self):
        """If the optimizer's step counter was changed externally (e.g.
        set_state_dict on checkpoint resume), re-seed the device-carried
        step so bias-corrected rules don't restart from step 1."""
        if self._opt._step_count != self._host_step_mirror:
            self._carry = (jnp.asarray(float(self._opt._step_count),
                                       jnp.float32),
                           self._carry[1], self._carry[2])
            self._host_step_mirror = self._opt._step_count

    @property
    def skipped_steps(self) -> int:
        """Steps the ``skip_nonfinite`` guard turned into identity
        updates. Carried on device (no per-step sync); reading blocks on
        the last dispatched step."""
        return int(np.asarray(self._carry[2]))

    @staticmethod
    def _commit(d):
        """Batches arrive UNCOMMITTED from jnp.asarray/to_tensor, and a
        single uncommitted argument pushes the whole dispatch onto jax's
        python slow path (the module-docstring trap, measured again
        2026-07: ~20% step-time penalty on ResNet-50). device_put onto
        the device the array already occupies is copy-free."""
        if getattr(d, "committed", True) or not hasattr(d, "devices"):
            return d
        try:
            return jax.device_put(d, next(iter(d.devices())))
        except Exception:
            return d

    def _compute_loss(self, model_outs, batch, n_inputs):
        """loss_fn(outputs..., labels...) — by convention the model consumes
        the leading batch elements and loss_fn the trailing ones; we pass
        (model_out, *remaining) where remaining = batch[n_model_inputs:]."""
        labels = [Tensor._from_data(b) for b in batch[n_inputs:]]
        outs = list(model_outs)
        return self._loss_fn(*(outs + labels))

    def __call__(self, *batch, n_model_inputs: Optional[int] = None):
        """batch = (model_inputs..., labels...). By default the model takes
        one input and the rest are labels."""
        # the host's time in this call; the dispatch is asynchronous, so
        # this is not the device step
        with span("train.step", step=self._opt._step_count):
            n_inputs = 1 if n_model_inputs is None else n_model_inputs
            datas = tuple(
                self._commit(b._data if isinstance(b, Tensor)
                             else jnp.asarray(b)) for b in batch)
            self._sync_step_carry()
            self._opt._step_count += 1  # host mirror (schedulers, state_dict)
            self._host_step_mirror = self._opt._step_count
            lr_val = float(self._opt.get_lr())
            if self._lr_arr is None or lr_val != self._lr_val:
                self._lr_val = lr_val
                self._lr_arr = jax.device_put(np.float32(lr_val))

            if self._sot_cache is None:
                try:
                    return self._run(self._jitted, n_inputs, datas)
                except jax.errors.ConcretizationTypeError:
                    # data-dependent Python control flow: switch this step to
                    # SOT guard-path specialization (jit/sot.py)
                    from paddle_tpu.jit.sot import PathCache

                    self._sot_cache = PathCache()
            return self._sot_call(n_inputs, datas)

    def run_steps(self, k, *batch, n_model_inputs: Optional[int] = None,
                  stacked: bool = False):
        """Run ``k`` optimizer steps in ONE compiled dispatch
        (``lax.scan`` over the step body) and return the (k,) loss vector.

        With ``stacked=True`` every batch array carries a leading ``k``
        dim (one microbatch per step); otherwise the same batch is
        re-used each step (e.g. steady-state benchmarking). Stacking is
        explicit, not inferred — a batch dim that happens to equal ``k``
        must not silently change semantics. This is the standard TPU pattern
        for host-latency-bound steps: a small model's ~1 ms step pays the
        host's dispatch cost every call, so k steps per dispatch raises
        throughput by up to k× with identical numerics (July-2026 chip run,
        ResNet-50: 9,268 img/s at k=1 vs 36,314 at k=32). The reference's analog is
        the static-graph executor running the whole Program without
        returning to Python each op (SURVEY.md §3.3).

        Semantics: the LR is read once per dispatch (host schedulers see
        one ``k``-step tick); state/RNG threading is identical to k
        ``__call__``s. Not available on SOT graph-break paths (falls back
        to a Python loop)."""
        n_inputs = 1 if n_model_inputs is None else n_model_inputs
        datas = tuple(
            self._commit(b._data if isinstance(b, Tensor)
                         else jnp.asarray(b)) for b in batch)
        if stacked:
            bad = [tuple(d.shape) for d in datas
                   if d.ndim == 0 or d.shape[0] != k]
            if bad:
                raise ValueError(
                    f"run_steps(stacked=True) needs a leading dim of {k} "
                    f"on every batch array; got shapes {bad}")

        def loop_fallback():
            # per-step dispatch keeps the documented k-__call__ numerics;
            # stacked batches are sliced per step
            losses = []
            for i in range(k):
                b_i = [d[i] for d in datas] if stacked else list(datas)
                losses.append(self.__call__(
                    *b_i, n_model_inputs=n_model_inputs))
            return Tensor._from_data(
                jnp.stack([l._data for l in losses]))

        if self._sot_cache is not None:
            return loop_fallback()
        self._sync_step_carry()
        lr_val = float(self._opt.get_lr())
        if self._lr_arr is None or lr_val != self._lr_val:
            self._lr_val = lr_val
            self._lr_arr = jax.device_put(np.float32(lr_val))

        jitted = self._multi_jitted.get((k, stacked))
        if jitted is None:
            raw = self._make_raw(None)

            def multi_fn(n_inputs, carry, param_datas, slot_list,
                         buffer_datas, lr, scaler_state, *batch):
                def body(state, xs):
                    c, params, slots, bufs, sstate = state
                    b = xs if xs is not None else batch
                    loss, c, params, slots, bufs, sstate, valid = raw(
                        n_inputs, c, params, slots, bufs, lr, sstate, *b)
                    return (c, params, slots, bufs, sstate), loss

                init = (carry, list(param_datas), list(slot_list),
                        list(buffer_datas), scaler_state)
                xs = list(batch) if stacked else None
                (c, params, slots, bufs, sstate), losses = jax.lax.scan(
                    body, init, xs, length=None if stacked else k)
                return losses, c, params, slots, bufs, sstate, \
                    jnp.asarray(True)

            jitted = jax.jit(multi_fn, static_argnums=(0,),
                             donate_argnums=self._donate_argnums())
            self._multi_jitted[(k, stacked)] = jitted
        try:
            losses = self._run(jitted, n_inputs, datas)
        except jax.errors.ConcretizationTypeError:
            # data-dependent Python control flow: scan can't trace it —
            # fall back to per-step SOT dispatch (__call__ bumps counters)
            from paddle_tpu.jit.sot import PathCache

            self._sot_cache = self._sot_cache or PathCache()
            return loop_fallback()
        # counters advance only after a successful dispatch
        self._opt._step_count += k
        self._host_step_mirror = self._opt._step_count
        return losses

    def _run(self, jitted, n_inputs, datas):
        """Dispatch one compiled step and rebind carried state."""
        from paddle_tpu.distributed.watchdog import arm_step, attach_step

        from paddle_tpu.distributed.watchdog import default_watchdog

        if self._dispatch_failed:
            # a previous dispatch failed; if it had consumed the donated
            # state, a retry would hit jax's raw "Array has been
            # deleted" — fail with the designed message instead. The
            # flag keeps the happy path free of per-step O(params)
            # is_deleted() sweeps.
            self._check_donated_state("re-dispatch after a failed step")
            self._dispatch_failed = False
        param_datas = [p._data for p in self._params]
        buffer_datas = [b._data for b in self._buffers]
        # a call that will trace+compile (first call, or new batch
        # shapes forcing a retrace) gets a stretched deadline — compile
        # is slow, not hung
        shapes = tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        warm = self._wd_warm.get(id(jitted)) == shapes
        wd_id = arm_step(f"TrainStep#{self._opt._step_count}",
                         cold=not warm)
        try:
            args = (n_inputs, self._carry, param_datas, self._slots,
                    buffer_datas, self._lr_arr, self._scaler_state, *datas)
            if jitted is self._jitted:
                # one is_enabled() with no profiler session live; under
                # one, the step stays readable after the run
                if not warm:
                    self._step_program.note(args)
                self._step_program.dispatched(self)
            with span("train.dispatch", cold=int(not warm)):
                loss, self._carry, new_params, new_slots, new_buffers, \
                    new_scaler_state, valid = jitted(*args)
        except BaseException:
            # failed dispatch must not leave an armed deadline behind
            default_watchdog().disarm(wd_id)
            # trace-time failures (ConcretizationTypeError -> SOT switch,
            # retrace on new shapes) never executed, so the donated state
            # is still live and the caller may re-dispatch; an
            # execution-time failure after donation is flagged but must
            # not mask the original error — the next _run raises the
            # designed guard error instead of jax's deleted-array one
            self._dispatch_failed = True
            self._warn_donated_state("failed dispatch")
            raise
        self._wd_warm[id(jitted)] = shapes
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
        self._last_valid = valid
        return Tensor._from_data(loss)

    def _explore(self, n_inputs, datas):
        """Eager forward of model+loss recording the guard path. Buffers
        are restored afterwards (the compiled step threads them)."""
        from paddle_tpu.autograd import engine as _engine
        from paddle_tpu.jit import sot as _sot

        # guard-miss path: the discarded dispatch DONATED the old state
        # arrays and _run rebound the re-materialized (value-identical)
        # outputs; the eager explore must see live buffers
        self._check_donated_state("eager explore after guard miss")
        saved_buf = [b._data for b in self._buffers]
        try:
            with _engine.no_grad(), _sot.recording() as rec:
                ins = [Tensor._from_data(d) for d in datas[:n_inputs]]
                out = self._model(*ins)
                outs = out if isinstance(out, tuple) else (out,)
                self._compute_loss(list(outs), datas, n_inputs)
        finally:
            for b, d in zip(self._buffers, saved_buf):
                b._data = d
        return tuple(rec.outcomes)

    def _sot_call(self, n_inputs, datas):
        cache = self._sot_cache
        key = cache.mru
        if key is not None:
            loss = self._run(cache.get(key), n_inputs, datas)
            if bool(self._last_valid):
                cache.touch(key)
                return loss
            cache.guard_mismatches += 1
        # explore the actual path, then run its specialization
        outcomes = self._explore(n_inputs, datas)
        fn = cache.get(outcomes)
        if fn is None:
            fn = self._make_jitted(outcomes)
            cache.put(outcomes, fn)
        else:
            cache.touch(outcomes)
        loss = self._run(fn, n_inputs, datas)
        if not bool(self._last_valid):
            raise RuntimeError(
                "sot: guard path diverged between eager explore and "
                "compiled replay on the same batch — the model's Python "
                "is not deterministic given (params, inputs)")
        return loss


def _merge(full, trainable_vals, mask):
    out = list(full)
    it = iter(trainable_vals)
    for i, t in enumerate(mask):
        if t:
            out[i] = next(it)
    return out
