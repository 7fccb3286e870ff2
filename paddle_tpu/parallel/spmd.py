"""SPMD hybrid-parallel train step — DP × TP × ZeRO × SP via GSPMD.

Reference parity: this one engine replaces several reference subsystems:
  - DP: dygraph `Reducer` bucketed allreduce (`imperative/reducer.cc`) — here
    gradients are reduced by XLA collectives fused into the backward;
  - TP: `TensorParallel` + mp_layers manual collectives — here sharding
    annotations (mp_layers.py) + GSPMD propagation;
  - ZeRO 1/2/3: `DygraphShardingOptimizer` / ShardingStage2/3
    (`fleet/meta_parallel/sharding/`) — here PartitionSpecs on optimizer
    slots (stage1/2) and parameters (stage3); XLA emits the reduce-scatter +
    all-gather pattern with buffer donation standing in for param2buffer
    slicing (`sharding_stage3.py:308-348`);
  - AMP O2: params kept fp32, cast to bf16 inside the step (master weights).

One `jax.jit` with in/out shardings over the HybridCommunicateGroup mesh:
forward + backward + optimizer in a single XLA program, collectives on ICI.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import monitor as _monitor
from .. import obs as _obs
from ..obs import memory as _mem
from ..core import compile_cache as _cc
from ..core import executable as _exe
from ..core import random as rnd
from ..core.tensor import Tensor
from ..jit.functional import functional_call, split_state
from .topology import get_mesh


def _shard_biggest_axis(shape, axis_name, axis_size):
    """Pick the largest dim divisible by axis_size to shard (ZeRO slicing)."""
    if not shape:
        return None
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % axis_size == 0 and shape[i] >= axis_size:
            spec = [None] * len(shape)
            spec[i] = axis_name
            return tuple(spec)
    return None


class SPMDTrainStep:
    def __init__(self, model, loss_fn: Callable, optimizer, mesh: Optional[Mesh] = None,
                 sharding_stage: int = 0, amp_dtype=None, donate: bool = True,
                 batch_specs: Optional[Sequence] = None, n_model_inputs=None,
                 grad_reduction: str = "gspmd",
                 bucket_bytes: Optional[int] = None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh or get_mesh()
        if self.mesh is None:
            raise ValueError("SPMDTrainStep requires a mesh (fleet.init or create_mesh)")
        self.sharding_stage = sharding_stage
        self.amp_dtype = amp_dtype
        self._donate = donate
        self._batch_specs = batch_specs
        self._n_model_inputs = n_model_inputs
        # "gspmd": the compiler inserts/fuses the gradient reduction.
        # "bucketed": explicit backward-interleaved per-bucket allreduce via
        # parallel.reducer.Reducer inside shard_map over the dp axis (the
        # reference imperative Reducer role) — the collectives are visible
        # to collective_signature()/tpu-lint instead of compiler-hidden.
        if grad_reduction not in ("gspmd", "bucketed"):
            raise ValueError(f"grad_reduction must be 'gspmd' or 'bucketed', "
                             f"got {grad_reduction!r}")
        self.grad_reduction = grad_reduction
        self._bucket_bytes = bucket_bytes  # None -> FLAGS_dp_bucket_mb
        self.reducer = None
        self._jitted = None
        self._slots = None
        # per-step device scalars: lr re-uploads only on value change, the
        # step counter t rides as donated carry state through the program
        self._lr_arr = None
        self._lr_host = None
        self._t_arr = None
        self._t_host = None
        # executable substrate: batch-signature ledger (novelty + retrace
        # accounting — previously only the FIRST build was attributed) and
        # per-signature persistent-cache callables
        self._ledger = _exe.ExecutableLedger("spmd_train_step")

    # ---- sharding policies ----
    def _data_axes(self):
        axes = [a for a in ("dp", "sharding") if a in self.mesh.shape]
        return tuple(axes) if axes else None

    def _param_spec(self, p):
        if p.dist_attr is not None:
            spec = tuple(a if (a is None or a in self.mesh.shape) else None
                         for a in p.dist_attr)
            if self.sharding_stage >= 3 and "sharding" in self.mesh.shape and \
                    all(a is None for a in spec):
                s3 = _shard_biggest_axis(tuple(p.shape), "sharding",
                                         self.mesh.shape["sharding"])
                return P(*s3) if s3 else P(*spec)
            return P(*spec)
        if self.sharding_stage >= 3 and "sharding" in self.mesh.shape:
            s3 = _shard_biggest_axis(tuple(p.shape), "sharding",
                                     self.mesh.shape["sharding"])
            if s3:
                return P(*s3)
        return P()

    def _slot_spec(self, p, pspec):
        if self.sharding_stage >= 1 and "sharding" in self.mesh.shape:
            if self.sharding_stage >= 3:
                return pspec  # slots follow sharded params
            s = _shard_biggest_axis(tuple(p.shape), "sharding",
                                    self.mesh.shape["sharding"])
            if s:
                return P(*s)
        return pspec

    def _batch_spec(self, ndim, i):
        if self._batch_specs is not None and i < len(self._batch_specs):
            sp = self._batch_specs[i]
            return sp if isinstance(sp, P) else P(*sp)
        ax = self._data_axes()
        return P(ax) if ax else P()

    # ---- build ----
    def _build(self, batch_arrs):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        trainable, frozen = split_state(model)
        self._pnames, self._bnames = list(trainable), list(frozen)
        ptensors = [trainable[n] for n in self._pnames]
        btensors = [frozen[n] for n in self._bnames]
        optimizer._parameter_list = optimizer._parameter_list or ptensors
        self._slots = optimizer.init_state(ptensors)
        pnames, bnames = self._pnames, self._bnames
        amp_dtype = self.amp_dtype
        mesh = self.mesh
        # jitted-path FLAGS_check_nan_inf (see jit/train_step.py): finite
        # flags traced into the SPMD executable, captured at build time
        from ..core import flags as _flags
        nan_check = bool(_flags.flag("check_nan_inf"))
        self._nan_check = nan_check

        pspecs = [self._param_spec(p) for p in ptensors]
        sspecs = [{k: self._slot_spec(p, ps) for k in s}
                  for p, ps, s in zip(ptensors, pspecs, self._slots)]
        bspecs = [P() for _ in btensors]
        n_mi = self._n_model_inputs
        if n_mi is None:
            n_mi = len(batch_arrs) if len(batch_arrs) <= 1 else len(batch_arrs) - 1
        self._n_mi = n_mi
        in_batch_specs = [self._batch_spec(a.ndim, i) for i, a in enumerate(batch_arrs)]

        def step_body(params, slots, buffers, step_key, lr, t, inputs,
                      labels, reducer=None):
            """Shared fwd+bwd+update core. With a reducer, grads are
            reduced per size-capped bucket in backward order (explicit
            collectives the latency-hiding scheduler can overlap with the
            remaining backward); without one, GSPMD owns the reduction."""
            rnd.push_trace_key(step_key)
            try:
                def fwd(ps):
                    from ..jit.functional import amp_functional_call
                    out = amp_functional_call(model, pnames, ps, bnames,
                                              buffers, inputs, amp_dtype)
                    outs = [Tensor(o) for o in out] if isinstance(out, (list, tuple)) \
                        else [Tensor(out)]
                    loss = loss_fn(*outs, *[Tensor(l) for l in labels])
                    return loss._value if isinstance(loss, Tensor) else loss

                loss, grads = jax.value_and_grad(fwd)(params)
                if reducer is not None:
                    grads = reducer.reduce(grads)
                    from jax import lax as _lax
                    loss = _lax.pmean(loss, reducer.axis)
                new_params, new_slots = optimizer.functional_update(
                    params, grads, slots, lr, t, params_meta=ptensors)
                if nan_check:
                    bad = jnp.stack(
                        [~jnp.isfinite(loss)]
                        + [~jnp.all(jnp.isfinite(g)) for g in grads])
                    return new_params, new_slots, loss, t + 1.0, bad
                return new_params, new_slots, loss, t + 1.0, None
            finally:
                rnd.pop_trace_key()

        use_reducer = self.grad_reduction == "bucketed"
        if use_reducer:
            if "dp" not in mesh.shape:
                raise ValueError("grad_reduction='bucketed' needs a 'dp' "
                                 "mesh axis (the reducer allreduces over it)")
            if self.sharding_stage != 0 or len(mesh.shape) != 1:
                raise ValueError(
                    "grad_reduction='bucketed' supports the pure-DP regime "
                    "(1-axis dp mesh, sharding_stage=0); hybrid layouts use "
                    "grad_reduction='gspmd' where the compiler owns the "
                    "reduction")
            bad_specs = [n for n, s in zip(self._pnames, pspecs) if s != P()]
            if bad_specs:
                raise ValueError("bucketed reduction requires replicated "
                                 f"params; sharded: {bad_specs[:3]}")
            from .reducer import Reducer
            self.reducer = Reducer(ptensors, axis="dp",
                                   bucket_bytes=self._bucket_bytes)

            def pure(params, slots, buffers, rng_key, lr, t, batch):
                from jax import shard_map

                def body(params, slots, buffers, rng_key, lr, t, *batch):
                    inputs, labels = batch[:n_mi], batch[n_mi:]
                    return step_body(params, slots, buffers, rng_key, lr, t,
                                     inputs, labels, reducer=self.reducer)

                in_specs = ([P() for _ in params],
                            [{k: P() for k in d} for d in slots],
                            [P() for _ in buffers],
                            P(), P(), P(),
                            *[P(*s) if not isinstance(s, P) else s
                              for s in in_batch_specs])
                out_specs = ([P() for _ in params],
                             [{k: P() for k in d} for d in slots],
                             P(), P(),
                             P() if nan_check else None)
                return shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)(
                    params, slots, buffers, rng_key, lr, t, *batch)
        else:
            def pure(params, slots, buffers, rng_key, lr, t, batch):
                inputs, labels = batch[:n_mi], batch[n_mi:]
                return step_body(params, slots, buffers, rng_key, lr, t,
                                 inputs, labels)

        def ns(spec):
            return NamedSharding(mesh, spec)

        in_sh = ([ns(s) for s in pspecs],
                 [{k: ns(v) for k, v in d.items()} for d in sspecs],
                 [ns(s) for s in bspecs],
                 None, ns(P()), ns(P()),
                 [ns(s) for s in in_batch_specs])
        out_sh = ([ns(s) for s in pspecs],
                  [{k: ns(v) for k, v in d.items()} for d in sspecs],
                  ns(P()),
                  ns(P()),
                  ns(P()) if nan_check else None)
        # donate params (0), slots (1) and the t carry (5)
        donate = (0, 1, 5) if self._donate else ()
        self._donate_argnums = donate
        self._pure = pure   # unjitted typed-key body: collective_signature
        # persistent-cache mode: raw-key-data program boundary (see
        # TrainStep._build)
        self._raw_key = _cc.enabled()
        jit_pure = pure
        if self._raw_key:
            def jit_pure(params, slots, buffers, key_data, lr, t, batch):
                return pure(params, slots, buffers,
                            jax.random.wrap_key_data(key_data), lr, t, batch)
        # the program's name in a trace's `XLA Modules`: jit_spmd_train_step
        jit_pure.__name__ = jit_pure.__qualname__ = "spmd_train_step"
        self._jitted = jax.jit(jit_pure, in_shardings=in_sh,
                               out_shardings=out_sh, donate_argnums=donate)
        self._pspecs = pspecs
        self._sspecs = sspecs
        from .. import analysis as _analysis
        if _analysis._ENABLED:
            _analysis.lint_traced(getattr(model, "forward", model),
                                  "spmd_train_step")
            _analysis.lint_traced(loss_fn, "spmd_train_step")

        # place params/slots/buffers on the mesh once (avoids per-step resharding)
        for p, spec in zip(ptensors, pspecs):
            p._value = jax.device_put(p._value, ns(spec))
        self._slots = [{k: jax.device_put(v, ns(d[k])) for k, v in s.items()}
                       for s, d in zip(self._slots, sspecs)]
        for b, spec in zip(btensors, bspecs):
            b._value = jax.device_put(b._value, ns(spec))
        pending = getattr(self, "_pending_state", None)
        if pending is not None:  # set_state_dict before the first step
            self._pending_state = None
            self._apply_state(pending)
        if _mem._ENABLED:
            self._tag_state()

    def _tag_state(self):
        """(Re-)tag the mesh-resident loop state for the live-buffer census
        (donation kills the old buffers' tags — see TrainStep._tag_state)."""
        trainable, frozen = split_state(self.model)
        _mem.tag("params", [trainable[n]._value for n in self._pnames],
                 origin="SPMDTrainStep")
        _mem.tag("opt_slots", self._slots, origin="SPMDTrainStep")
        _mem.tag("model_buffers", [frozen[n]._value for n in self._bnames],
                 origin="SPMDTrainStep")
        if self._t_arr is not None:
            _mem.tag("step_state", [self._t_arr], origin="SPMDTrainStep")

    def collective_signature(self, *batch):
        """The step's static collective sequence (tpu-lint collective-order
        rule): trace the unjitted step body and extract every explicit
        collective as `analysis.graph.CollectiveDesc`s. Feed the per-rank /
        per-stage results to `analysis.verify_collective_order` to prove
        the sequences agree BEFORE a pod slice deadlocks on a divergence.
        (GSPMD-inserted collectives are compiler-chosen and not part of the
        static signature; explicit ones — mp/pp/sp ops traced through
        `parallel.collective` inside shard_map regions — are.)"""
        arrs = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        if self._jitted is None:
            self._build(arrs)
        trainable, frozen = split_state(self.model)
        params = [trainable[n]._value for n in self._pnames]
        buffers = [frozen[n]._value for n in self._bnames]
        key = rnd.default_generator().next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        t = jnp.asarray(self.optimizer._step_count + 1, jnp.float32)
        from ..analysis.graph import collective_sequence
        return collective_sequence(self._pure, params, self._slots, buffers,
                                   key, lr, t, arrs)

    # ---- full loop-state capture (guard plane: preemption-safe resume) ----
    def named_param_arrays(self):
        """name -> device array for every trainable param (desync
        fingerprints; no copy)."""
        trainable, _ = split_state(self.model)
        names = self._pnames if self._jitted is not None else list(trainable)
        return {n: trainable[n]._value for n in names}

    def state_dict(self):
        """Host-side copy of params + optimizer slots + step counter. The
        per-step rng key is drawn from the global generator (capture it
        with core.random.get_rng_state alongside this dict — the guard
        checkpoint does)."""
        if self._jitted is None:
            raise RuntimeError("SPMDTrainStep.state_dict() requires a built "
                               "step — run at least one step first")
        trainable, _ = split_state(self.model)
        return {
            "kind": "spmd_train_step",
            "params": {n: np.asarray(trainable[n]._value)
                       for n in self._pnames},
            "slots": [{k: np.asarray(v) for k, v in s.items()}
                      for s in self._slots],
            "step_count": int(self.optimizer._step_count),
        }

    def set_state_dict(self, sd):
        if self._jitted is None:
            # applied at the end of _build, after shardings exist
            self._pending_state = sd
            self.optimizer._step_count = int(sd["step_count"])
            return
        self._apply_state(sd)

    def _apply_state(self, sd):
        from jax.sharding import NamedSharding

        def ns(spec):
            return NamedSharding(self.mesh, spec)

        trainable, _ = split_state(self.model)
        params = sd["params"]
        for n, spec in zip(self._pnames, self._pspecs):
            if n in params:
                trainable[n]._value = jax.device_put(
                    jnp.asarray(params[n]), ns(spec))
        self._slots = [{k: jax.device_put(jnp.asarray(v), ns(d[k]))
                        for k, v in s.items()}
                       for s, d in zip(sd["slots"], self._sspecs)]
        self.optimizer._step_count = int(sd["step_count"])

    # ---- per-step device scalars (no fresh float() feeds per step) ----
    def _lr_scalar(self):
        """lr as a mesh-replicated cached scalar: H2D only on value change."""
        lr_val = self.optimizer.get_lr()
        if lr_val != self._lr_host or self._lr_arr is None:
            self._lr_host = lr_val
            self._lr_arr = jax.device_put(
                jnp.asarray(lr_val, jnp.float32),
                NamedSharding(self.mesh, P()))
        return self._lr_arr

    def _t_scalar(self):
        """Step counter as donated device carry (the program returns t+1);
        the host mirror catches external _step_count writes (guard
        rollback/resume) and refreshes the carry from the host."""
        expected = float(self.optimizer._step_count + 1)
        if self._t_arr is None or self._t_host != expected:
            self._t_arr = jax.device_put(
                jnp.asarray(expected, jnp.float32),
                NamedSharding(self.mesh, P()))
            self._t_host = expected
        return self._t_arr

    def input_shardings(self, *batch):
        """NamedShardings for the step's batch arguments — what the
        io.prefetch feeder uses so its device_put stages each batch
        DIRECTLY into the layout the executable consumes (no resharding
        on the step's critical path). Builds the step if needed."""
        arrs = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        if self._jitted is None:
            self._build(arrs)
        return [NamedSharding(self.mesh, self._batch_spec(a.ndim, i))
                for i, a in enumerate(arrs)]

    def __call__(self, *batch):
        with _obs.step_record():
            with _obs.phase("h2d"):
                arrs = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                        for b in batch]
            first = self._jitted is None
            if first:
                with _obs.phase("build"):
                    self._build(arrs)
            trainable, frozen = split_state(self.model)
            params = [trainable[n]._value for n in self._pnames]
            buffers = [frozen[n]._value for n in self._bnames]
            key = rnd.default_generator().next_key()
            if self._raw_key:
                key = jax.random.key_data(key)
            lr = self._lr_scalar()
            t = self._t_scalar()
            if _mem._ENABLED:
                _mem.tag("activations", arrs, origin="SPMDTrainStep.batch")
            sig, novel = None, first
            if _monitor._ENABLED or _obs._TL_ENABLED or _cc.enabled():
                sig = _monitor.arg_signature(arrs)
                novel = self._ledger.note(sig)
            # GSPMD folds the collectives INTO the executable, so the
            # timeline cannot fence them apart from compute here — the
            # device_compute phase is the whole sharded step; explicit
            # eager collectives (parallel/collective.py) get their own
            # `collective` phase.
            with _exe.booking("spmd_train_step") as bk:
                call = self._jitted
                if sig is not None:
                    cached = self._ledger.get(sig)
                    if cached is not None:
                        call = cached
                    elif novel:
                        if _cc.enabled():
                            call, source = _exe.acquire(
                                "spmd_train_step", self._jitted,
                                (params, self._slots, buffers, key, lr, t,
                                 arrs),
                                donate=self._donate_argnums,
                                label="SPMDTrainStep",
                                mesh_shape=dict(self.mesh.shape))
                            self._ledger.put(sig, call)
                            if source == "fresh":
                                bk.compiled()
                        else:
                            bk.compiled()
                elif first:
                    bk.compiled()
                with _exe.dispatch_guard(
                        "SPMDTrainStep",
                        report=lambda: _obs.executable_memory(
                            self._jitted.lower(params, self._slots, buffers,
                                               key, lr, t, arrs).compile())):
                    new_params, self._slots, loss, new_t, bad = call(
                        params, self._slots, buffers, key, lr, t, arrs)
                if _obs._TL_ENABLED:
                    jax.block_until_ready(loss)
            # commit before the debug raise — old buffers were donated
            for n, v in zip(self._pnames, new_params):
                trainable[n]._value = v
            self._t_arr = new_t
            self._t_host = self._t_host + 1.0
            self.optimizer._step_count += 1
            if _mem._ENABLED:
                self._tag_state()
            from ..jit.train_step import raise_nonfinite
            raise_nonfinite(bad, self._pnames, "jitted SPMD train step")
            return Tensor(loss)

    def compiled(self, *batch):
        """The `jax.stages.Compiled` sharded step executable at `batch`'s
        signature (see jit.TrainStep.compiled). `.as_text()` shows the
        collectives GSPMD put in."""
        arrs = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        if self._jitted is None:
            self._build(arrs)
        trainable, frozen = split_state(self.model)
        params = [trainable[n]._value for n in self._pnames]
        buffers = [frozen[n]._value for n in self._bnames]
        key = rnd.default_generator().next_key()
        if self._raw_key:
            key = jax.random.key_data(key)
        return self._jitted.lower(params, self._slots, buffers, key,
                                  self._lr_scalar(), self._t_scalar(),
                                  arrs).compile()

    def cost_analysis(self, *batch):
        """Compiler-attributed {flops, bytes_accessed} for the sharded step
        executable (see jit.TrainStep.cost_analysis). Per-device numbers:
        XLA reports the cost of one shard of the SPMD program."""
        return _obs.executable_cost(self.compiled(*batch))

    def memory_report(self, *batch):
        """Compiler-reported memory breakdown for the sharded step
        executable (see jit.TrainStep.memory_report). Per-device numbers:
        XLA reports one shard of the SPMD program."""
        return _obs.executable_memory(self.compiled(*batch))
