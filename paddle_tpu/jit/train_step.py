"""Jitted whole-train-step builder — the TPU performance path.

Reference parity: this plays the role of the reference's static-graph
training program (forward + append_backward + optimizer ops compiled as one
ProgramDesc, SURVEY §3.1): ONE XLA executable for forward+backward+update,
with buffer donation on parameters and optimizer state (the XLA answer to
fluid's in-place Variable updates).

Usage:
    step = TrainStep(model, loss_fn, optimizer)
    loss = step(x, y)        # tensors in, python float-able loss out
"""
from __future__ import annotations

from typing import Callable, Optional

import time as _time

import jax
import jax.numpy as jnp

from .. import analysis as _analysis
from .. import monitor as _monitor
from .. import obs as _obs
from ..obs import memory as _mem
from ..core import compile_cache as _cc
from ..core import executable as _exe
from ..core import random as rnd
from ..core.tensor import Tensor
from .functional import functional_call, split_state


def raise_nonfinite(bad, pnames, context):
    """Decode the in-program finite flags ([P+1] or [n_steps, P+1]) and
    raise naming the offending grads (reference per-op abort,
    operator.cc:1171). No-op when the check wasn't traced (bad is None).
    Callers must have committed params/slots/step state FIRST — the jit
    call donated the old buffers."""
    if bad is None:
        return
    import numpy as np_
    flags_arr = np_.asarray(bad)
    if flags_arr.ndim == 2:              # scan: [n_steps, P+1] -> any step
        flags_arr = flags_arr.any(axis=0)
    if not flags_arr.any():
        return
    names = ["loss" if i == 0 else f"grad of {pnames[i - 1]}"
             for i in np_.nonzero(flags_arr)[0]]
    raise FloatingPointError(
        f"NaN/Inf detected in {context} "
        f"(FLAGS_check_nan_inf=True): {', '.join(names)}")


class TrainStep:
    def __init__(self, model, loss_fn: Callable, optimizer, amp_dtype=None,
                 donate: bool = True, mesh=None, in_shardings=None,
                 n_model_inputs: Optional[int] = None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_dtype = amp_dtype
        self._jitted = None
        self._donate = donate
        self._slots = None
        self._pnames = None
        self._bnames = None
        # step(x..., y...): first n go to model.forward, the rest to loss_fn
        self._n_model_inputs = n_model_inputs
        # executable substrate: signature ledger (novelty + retrace
        # accounting) and per-signature cached callables (persistent-cache
        # deserialized executables) — one implementation for all regimes
        self._ledger = _exe.ExecutableLedger("train_step")

    def _build(self):
        from ..core import flags as _flags
        if _monitor._ENABLED:
            _monitor.count("jit.train_step.builds")
        if _analysis._ENABLED:
            # trace-time tpu-lint on the functions about to be traced into
            # the step executable (build runs once; __call__ pays nothing)
            _analysis.lint_traced(getattr(self.model, "forward", self.model),
                                  "train_step")
            _analysis.lint_traced(self.loss_fn, "train_step")
        # FLAGS_check_nan_inf for the COMPILED hot loop (operator.cc:1171
        # role): the per-op eager scan can't see inside a jitted step, so
        # the finite-check is traced INTO the executable — one fused
        # [P+1]-flag reduction over loss+grads, read back on host only in
        # debug mode. Flag is captured at build time (first step).
        self._nan_check = bool(_flags.flag("check_nan_inf"))
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        trainable, frozen = split_state(model)
        self._pnames, self._bnames = list(trainable), list(frozen)
        ptensors = [trainable[n] for n in self._pnames]
        # cache tensor objects: __call__ must not re-walk the module tree
        self._ptensors = ptensors
        self._btensors = [frozen[n] for n in self._bnames]
        optimizer._parameter_list = optimizer._parameter_list or ptensors
        self._slots = optimizer.init_state(ptensors)
        pnames, bnames = self._pnames, self._bnames
        amp_dtype = self.amp_dtype

        def one_step(params, slots, buffers, step_key, lr, t, inputs, labels):
            rnd.push_trace_key(step_key)
            try:
                def fwd(ps):
                    from .functional import amp_functional_call
                    out = amp_functional_call(model, pnames, ps, bnames,
                                              buffers, inputs, amp_dtype)
                    outs = [Tensor(o) for o in out] if isinstance(out, (list, tuple)) \
                        else [Tensor(out)]
                    loss = loss_fn(*outs, *[Tensor(l) for l in labels])
                    return loss._value if isinstance(loss, Tensor) else loss

                loss, grads = jax.value_and_grad(fwd)(params)
                new_params, new_slots = optimizer.functional_update(
                    params, grads, slots, lr, t, params_meta=ptensors)
                if self._nan_check:
                    bad = jnp.stack(
                        [~jnp.isfinite(loss)]
                        + [~jnp.all(jnp.isfinite(g)) for g in grads])
                    return new_params, new_slots, loss, bad
                return new_params, new_slots, loss, None
            finally:
                rnd.pop_trace_key()

        # the defs' names are the programs' (`jit_train_step` in a trace's
        # `XLA Modules`), in the raw-key variant too
        def train_step(params, slots, buffers, rng_key, lr, t, inputs,
                       labels):
            # rng advance + step counter live IN the program: zero per-step
            # host->device scalar traffic
            step_key, carry_key = jax.random.split(rng_key)
            new_params, new_slots, loss, bad = one_step(
                params, slots, buffers, step_key, lr, t, inputs, labels)
            return new_params, new_slots, loss, carry_key, t + 1.0, bad

        def train_step_scan(params, slots, buffers, rng_key, lr, t, inputs,
                            labels):
            # Device-side training loop: N steps inside ONE executable via
            # lax.scan — the TPU answer to the reference's C++ trainer hot
            # loop (framework/trainer.h:59, hogwild_worker.cc TrainFiles),
            # which likewise iterates steps without returning to the host.
            # inputs/labels are stacked [n_steps, ...]; weights/opt state
            # stay device-resident across the whole span.
            def body(carry, xs):
                params, slots, key, t = carry
                ins, labs = xs
                step_key, key = jax.random.split(key)
                new_params, new_slots, loss, bad = one_step(
                    params, slots, buffers, step_key, lr, t, ins, labs)
                return (new_params, new_slots, key, t + 1.0), (loss, bad)

            (params, slots, key, t), (losses, bads) = jax.lax.scan(
                body, (params, slots, rng_key, t), (list(inputs), list(labels)))
            return params, slots, losses, key, t, bads

        # Persistent-cache mode: when the compile cache is on the step
        # program takes/returns RAW key data (uint32) and wraps/unwraps at
        # the program boundary — numerics identical. (Written when
        # jax.export could not serialize typed PRNG key avals; jax 0.9
        # can, so this adapter is a candidate for removal: PERF.md §7.)
        self._raw_key = _cc.enabled()
        if self._raw_key:
            base_step, base_scan = train_step, train_step_scan

            def train_step(params, slots, buffers, key_data, lr, t, inputs,
                           labels):
                new_params, new_slots, loss, carry, t1, bad = base_step(
                    params, slots, buffers,
                    jax.random.wrap_key_data(key_data), lr, t, inputs, labels)
                return (new_params, new_slots, loss,
                        jax.random.key_data(carry), t1, bad)

            def train_step_scan(params, slots, buffers, key_data, lr, t,
                                inputs, labels):
                new_params, new_slots, losses, carry, t1, bads = base_scan(
                    params, slots, buffers,
                    jax.random.wrap_key_data(key_data), lr, t, inputs, labels)
                return (new_params, new_slots, losses,
                        jax.random.key_data(carry), t1, bads)

        donate = (0, 1, 3, 5) if self._donate else ()
        self._donate_argnums = donate
        self._jitted = jax.jit(train_step, donate_argnums=donate)
        self._jitted_scan = jax.jit(train_step_scan, donate_argnums=donate)
        self._key = rnd.default_generator().next_key()
        if self._raw_key:
            self._key = jax.random.key_data(self._key)
        self._t_arr = jnp.asarray(float(self.optimizer._step_count + 1),
                                  jnp.float32)
        self._lr_val = None
        self._lr_arr = None
        if _mem._ENABLED:
            self._tag_state()

    def _tag_state(self):
        """(Re-)tag the loop state for the live-buffer census. Called after
        build AND after every commit: the jit call donates the old param /
        slot / step-state buffers, so their tags die with them and the
        replacement arrays must be claimed again."""
        _mem.tag("params", [t._value for t in self._ptensors],
                 origin="TrainStep")
        _mem.tag("opt_slots", self._slots, origin="TrainStep")
        _mem.tag("step_state", [self._key, self._t_arr], origin="TrainStep")
        _mem.tag("model_buffers", [t._value for t in self._btensors],
                 origin="TrainStep")

    def _prepare(self, batch):
        """Shared prep for __call__/run: param/buffer arrays, model-input vs
        label split, lr-array cache refresh. Returns the batch split plus a
        `novel` flag — True when this batch signature has not been
        dispatched before, i.e. the jitted call ahead pays trace+compile
        (the timeline attributes it to `trace_compile`, not compute)."""
        if self._jitted is None:
            with _obs.phase("build"):
                self._build()
        params = [t._value for t in self._ptensors]
        buffers = [t._value for t in self._btensors]
        with _obs.phase("h2d"):
            arrs = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                    for b in batch]
        if _mem._ENABLED:
            _mem.tag("activations", arrs, origin="TrainStep.batch")
        n_mi = self._n_model_inputs
        if n_mi is None:
            n_mi = len(arrs) if len(arrs) <= 1 else len(arrs) - 1
        lr_val = self.optimizer.get_lr()
        if lr_val != self._lr_val:
            self._lr_val = lr_val
            self._lr_arr = jnp.asarray(lr_val, jnp.float32)
        novel, sig = False, None
        if _monitor._ENABLED or _obs._TL_ENABLED or _cc.enabled():
            # retrace accounting: the jitted step recompiles for every novel
            # batch signature — the dominant TPU perf hazard. The signature
            # that caused each retrace is logged for diagnosis (and the
            # timeline books the compile under trace_compile). The ledger
            # also keys the persistent-cache callables per signature.
            sig = _monitor.arg_signature(arrs)
            novel = self._ledger.note(sig)
        return params, buffers, arrs[:n_mi], arrs[n_mi:], novel, sig

    def __call__(self, *batch):
        """batch: input tensors consumed by model.forward; loss_fn receives the
        model output(s) — close labels into loss_fn or pass them as model inputs.
        """
        with _obs.step_record():
            params, buffers, inputs, labels, novel, sig = self._prepare(batch)
            _mon = _monitor._ENABLED
            if _mon:
                _t0 = _time.time()
            _tl = _obs._TL_ENABLED
            with _exe.booking("train_step") as bk:
                call = self._jitted
                if sig is not None:
                    cached = self._ledger.get(sig)
                    if cached is not None:
                        call = cached
                    elif novel:
                        if _cc.enabled():
                            # persistent-cache build step: a prior
                            # process's serialized executable (zero
                            # compiles here), or export+persist ours
                            args = (params, self._slots, buffers,
                                    self._key, self._lr_arr, self._t_arr,
                                    inputs, labels)
                            call, source = _exe.acquire(
                                "train_step", self._jitted, args,
                                donate=self._donate_argnums,
                                label="TrainStep")
                            self._ledger.put(sig, call)
                            if source == "fresh":
                                bk.compiled()
                        else:
                            bk.compiled()
                # OOM forensics drill site (`mem.alloc`) + the
                # RESOURCE_EXHAUSTED dump on the way out of a failure
                with _exe.dispatch_guard(
                        "TrainStep",
                        report=lambda: _obs.executable_memory(
                            self._jitted.lower(
                                params, self._slots, buffers, self._key,
                                self._lr_arr, self._t_arr, inputs,
                                labels).compile())):
                    new_params, self._slots, loss, self._key, self._t_arr, \
                        bad = call(params, self._slots, buffers,
                                   self._key, self._lr_arr,
                                   self._t_arr, inputs, labels)
                if _tl:
                    # fence: on an async backend the dispatch above returns
                    # before the chip finishes; without this the device time
                    # would leak into whatever phase syncs next
                    jax.block_until_ready(loss)
            # commit ALL state before any debug raise: the old param buffers
            # were DONATED to the jit call, so bailing out early would leave
            # every tensor pointing at a deleted buffer (and slots/step_count
            # desynced)
            for tns, v in zip(self._ptensors, new_params):
                tns._value = v
            self.optimizer._step_count += 1
            if _mem._ENABLED:
                self._tag_state()
            if _mon:
                _monitor.count("jit.train_step.steps")
                _monitor.observe("jit.train_step.dur", _time.time() - _t0)
            raise_nonfinite(bad, self._pnames, "jitted train step")
            return Tensor(loss)

    def compiled(self, *batch):
        """The `jax.stages.Compiled` step executable at `batch`'s signature
        (AOT lower + compile; hits the same cache as __call__ for an
        already-dispatched signature). `.as_text()` is the optimized HLO —
        where a kernel (`tpu_custom_call`) or a collective shows."""
        params, buffers, inputs, labels, _, _sig = self._prepare(batch)
        return self._jitted.lower(params, self._slots, buffers, self._key,
                                  self._lr_arr, self._t_arr, inputs,
                                  labels).compile()

    def cost_analysis(self, *batch):
        """XLA's own cost estimate for THIS step executable at `batch`'s
        signature: {"flops", "bytes_accessed", ...} (obs/cost.py).
        bench.py uses it to report *attributed* MFU — the compiler-counted
        FLOPs over measured step time — next to the formula-derived one."""
        return _obs.executable_cost(self.compiled(*batch))

    def memory_report(self, *batch):
        """XLA's own memory breakdown for THIS step executable at `batch`'s
        signature: {"argument_bytes", "output_bytes", "temp_bytes",
        "alias_bytes", "generated_code_bytes", "peak_bytes"}
        (obs/memory.py). temp_bytes is the number OOM forensics cares
        about — the scratch HBM the step needs ON TOP of the live buffers
        the census can see."""
        return _obs.executable_memory(self.compiled(*batch))

    # ---- full loop-state capture (guard plane: preemption-safe resume) ----
    def named_param_arrays(self):
        """name -> device array for every trainable param (desync
        fingerprints; no copy)."""
        if self._jitted is None:
            self._build()
        return {n: t._value for n, t in zip(self._pnames, self._ptensors)}

    def state_dict(self):
        """Host-side copy of the FULL loop state: params, optimizer slots,
        the in-program rng carry key and step counter. `set_state_dict` of
        this dict reproduces the uninterrupted training stream
        bit-identically — the carry key is the exact key the next step
        would have split, not a reseeded approximation."""
        if self._jitted is None:
            self._build()
        import numpy as np_
        return {
            "kind": "train_step",
            "params": {n: np_.asarray(t._value)
                       for n, t in zip(self._pnames, self._ptensors)},
            "slots": [{k: np_.asarray(v) for k, v in s.items()}
                      for s in self._slots],
            "rng_key": np_.asarray(self._key if self._raw_key
                                   else jax.random.key_data(self._key)),
            "t": np_.asarray(self._t_arr),
            "step_count": int(self.optimizer._step_count),
        }

    def set_state_dict(self, sd):
        if self._jitted is None:
            self._build()
        params = sd["params"]
        for n, t in zip(self._pnames, self._ptensors):
            if n in params:
                t._value = jnp.asarray(params[n])
        self._slots = [{k: jnp.asarray(v) for k, v in s.items()}
                       for s in sd["slots"]]
        key_arr = jnp.asarray(sd["rng_key"])
        self._key = key_arr if self._raw_key \
            else jax.random.wrap_key_data(key_arr)
        self._t_arr = jnp.asarray(sd["t"], jnp.float32)
        self.optimizer._step_count = int(sd["step_count"])
        self._lr_val = None  # force the lr-array cache to refresh
        if _mem._ENABLED:
            self._tag_state()

    def run(self, *batch):
        """Device-side multi-step loop: every tensor in `batch` is stacked
        along a leading n_steps axis ([n, ...] per step-shape [...]); runs
        all n optimizer steps in one executable and returns the [n] loss
        history as a Tensor. One host dispatch + one sync per span instead
        of per step — the per-step dispatch tax disappears.
        """
        params, buffers, inputs, labels, _novel, _sig = self._prepare(batch)
        n_steps = int(inputs[0].shape[0]) if inputs else int(labels[0].shape[0])
        new_params, self._slots, losses, self._key, self._t_arr, bads = \
            self._jitted_scan(params, self._slots, buffers, self._key,
                              self._lr_arr, self._t_arr, inputs, labels)
        # commit before the debug raise (donated buffers — see __call__)
        for tns, v in zip(self._ptensors, new_params):
            tns._value = v
        self.optimizer._step_count += n_steps
        if _mem._ENABLED:
            self._tag_state()
        if _monitor._ENABLED:
            _monitor.count("jit.train_step.steps", n_steps)
        raise_nonfinite(bads, self._pnames, "jitted train step")
        return Tensor(losses)
