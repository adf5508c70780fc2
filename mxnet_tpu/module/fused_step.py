"""Single-dispatch fused train step.

The reference MXNet hides per-op latency behind its C++ dependency
engine, which overlaps data loading, per-parameter SGD updates and
kvstore reduces (SURVEY §1; the engine-scheduled ``ccSGD`` fused update
in src/optimizer/sgd-inl.h).  The TPU-idiomatic equivalent is to compile
the ENTIRE train step — forward, ``jax.vjp`` backward, gradient
rescale/clip and the optimizer update over the whole parameter/state
pytree — into one donated XLA program, so a training batch costs one
host dispatch instead of ``1 + num_params``.

:class:`FusedTrainStep` wraps a bound single-context :class:`Executor`
plus an optimizer exposing the pure functional ``step_param`` /
``step_tree`` surface (mxnet_tpu/optimizer.py).  Numerics match the
per-param loop by construction: both paths trace the same
``step_param``, the same schedule/multiplier plumbing computes lr/wd per
parameter on the host, and the update-count bookkeeping increments
exactly like the per-param loop so checkpoint-resume across paths is
seamless.  Weights and optimizer state are donated on TPU (mirroring
the optimizer module's ``_donate`` guard); on CPU XLA ignores donation,
so the path is still correct, just without in-place buffer reuse.

Selection lives in :meth:`Module._select_fused`; anything the fused
program cannot express — multiple contexts, kvstore reduction, custom
updaters, monitors, ``grad_req`` other than ``write``, optimizers
without ``step_param`` (SGLD's RNG operand) — falls back to the classic
forward/backward/per-param loop.
"""

from __future__ import annotations

import collections
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..aot import export_store as aot_store
from ..base import MXNetError, env_flag
from ..lint.annotations import hot_path
from ..ndarray import NDArray
from ..optimizer import (_dispatch_inc, _donate, _state_commit,
                         _state_leaves)
from ..telemetry import flight as flight_mod
from ..telemetry import statusz as statusz_mod

__all__ = ["FusedTrainStep", "note_selection", "selection_state"]

# -- fused-path selection log (the /statusz "why is training unfused?"
# answer): Module._select_fused records every verdict here ---------------------
_selections = collections.deque(maxlen=16)


def note_selection(selected, reason):
    """Record one fused-path eligibility verdict (Module._select_fused).
    Repeats of the same verdict fold into the last entry's ``count`` —
    a custom train loop re-scanning every batch logs one line, not
    sixteen."""
    if (_selections and _selections[-1]["selected"] == bool(selected)
            and _selections[-1]["reason"] == str(reason)):
        # mxtpu-lint: disable=wall-clock (statusz display timestamp)
        _selections[-1]["t"] = round(time.time(), 3)
        _selections[-1]["count"] = _selections[-1].get("count", 1) + 1
        return
    # mxtpu-lint: disable=wall-clock (statusz display timestamp)
    _selections.append({"t": round(time.time(), 3),
                        "selected": bool(selected), "reason": str(reason)})


def selection_state():
    """Recent verdicts, newest last — served under /statusz."""
    return {"recent": list(_selections),
            "fused_env_enabled": env_flag("MXTPU_FUSED_STEP"),
            "numeric_watch": env_flag("MXTPU_NUMERIC_WATCH", False)}


statusz_mod.register("train.fused_step", selection_state)


class FusedTrainStep:
    """One compiled XLA program per (executor, optimizer) doing
    forward + backward + whole-pytree optimizer update.

    ``step(data_batch)`` dispatches asynchronously (JAX async dispatch:
    the call returns before the device finishes), leaves the executor's
    outputs/aux/params rebound to the program's results, and keeps the
    updater's per-index optimizer state in sync with the per-param
    path's representation — so checkpointing and a later fallback to
    the classic loop see exactly the state they expect.
    """

    def __init__(self, executor, optimizer, updater, param_names,
                 data_names, label_names):
        self._exe = executor
        self._opt = optimizer
        self._updater = updater
        self._param_names = list(param_names)
        self._data_names = list(data_names)
        self._label_names = list(label_names)
        self._indices = {name: i for i, name in enumerate(param_names)}
        # trainable = params the executor holds gradients for, in
        # param order (the per-param loop's enumeration)
        self._trainable = [n for n in param_names
                           if n in executor._grad_names]
        if not self._trainable:
            raise MXNetError("fused step needs at least one trainable param")

        graph = executor._graph
        opt = optimizer
        # opt-in numeric watchdog (MXTPU_NUMERIC_WATCH): the program
        # additionally returns (outputs-finite, global grad norm) and
        # the host checks them — one forced sync per step, the price of
        # catching a NaN the step it appears instead of epochs later
        self._watch = env_flag("MXTPU_NUMERIC_WATCH", False)
        watch = self._watch

        def program(params, others, aux, states, key, lrs, wds, t):
            def f(p):
                outs, new_aux = graph({**p, **others}, aux, key, True)
                return outs, new_aux

            outs, vjp_fn, new_aux = jax.vjp(f, params, has_aux=True)
            # loss-layer head-grad contract: ones per output (the same
            # default the executor's fused fwd_bwd uses)
            head = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            grads = vjp_fn(head)[0]
            new_params, new_states = opt.step_tree(params, grads, states,
                                                   lrs, wds, t)
            if watch:
                outs_ok = jnp.asarray(True)
                for o in outs:
                    outs_ok = jnp.logical_and(outs_ok,
                                              jnp.isfinite(o).all())
                gsq = jnp.asarray(0.0, jnp.float32)
                for g in jax.tree_util.tree_leaves(grads):
                    gsq = gsq + jnp.sum(
                        jnp.square(g.astype(jnp.float32)))
                return (outs, new_params, new_states, new_aux,
                        outs_ok, jnp.sqrt(gsq))
            return outs, new_params, new_states, new_aux

        # donate weights (arg 0) and optimizer state (arg 3): on TPU the
        # update reuses their buffers in place, halving peak param memory
        self._program = jax.jit(program, donate_argnums=_donate(0, 3))
        # AOT restart path (mxnet_tpu/aot/): resolved lazily at the
        # first step, when the concrete arg shapes exist
        self._aot_resolved = self._aot_store() is None

    # -- AOT export/load (mxnet_tpu/aot/) ----------------------------------
    @staticmethod
    def _aot_store():
        return aot_store.default_store()

    def _aot_fingerprint(self, args):
        """What pins the traced fused program: the symbol graph, the
        optimizer's baked-in scalars (anything read at trace time —
        momentum, rescale_grad, clip — becomes a compiled constant),
        every leaf shape/dtype, and the donation policy.  lr/wd/t are
        runtime operands and deliberately absent."""
        opt = self._opt
        # num_update/begin_num_update are runtime operands (t), not
        # trace-time constants — keying on them would re-export on
        # every checkpoint resume.  np.generic covers numpy scalars
        # (rescale_grad=np.float32(...) is baked into the trace just
        # like a Python float and must key the artifact the same way).
        # mxtpu-lint: disable=host-sync (np.generic host scalars —
        # one-time AOT fingerprinting, no device values involved)
        baked = {k: (v.item() if isinstance(v, np.generic) else v)
                 for k, v in sorted(vars(opt).items())
                 if isinstance(v, (int, float, str, bool, type(None),
                                   np.generic))
                 and k not in ("num_update", "begin_num_update")}
        leaves = [(str(jax.tree_util.tree_structure(args)),)]
        for leaf in jax.tree_util.tree_leaves(args):
            leaves.append((tuple(getattr(leaf, "shape", ())),
                           str(getattr(leaf, "dtype", type(leaf)))))
        sym_hash = hashlib.sha256(
            self._exe._symbol.tojson().encode()).hexdigest()
        return aot_store.fingerprint(
            subsystem="fused_step", symbol=sym_hash,
            optimizer=type(opt).__name__, baked=baked, leaves=leaves,
            donate=list(_donate(0, 3)), numeric_watch=self._watch)

    def _resolve_aot(self, args):
        """Swap self._program for an AOT artifact (or write one): the
        restarted process deserializes instead of re-tracing forward+
        backward+update, and the XLA compile of the round-tripped
        module hits the persistent compile cache."""
        self._aot_resolved = True
        store = self._aot_store()
        if store is None:
            return
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        fp = self._aot_fingerprint(specs)
        exported = store.load(fp, label="fused-step")
        if exported is None:
            try:
                exported = jax.export.export(self._program)(*specs)
            except Exception:
                return                 # unexportable: keep the plain jit
            store.save(fp, exported, label="fused-step")
        self._program = jax.jit(exported.call,
                                donate_argnums=_donate(0, 3))

    # -- staging -----------------------------------------------------------
    def _as_device_value(self, src, bound, name):
        """Batch input -> jax array matching the bound array's
        shape/dtype on the executor's device (the contract
        ``arg_dict[name][:] = arr`` enforces on the classic path)."""
        if isinstance(src, NDArray):
            val = src._data
        else:
            # mxtpu-lint: disable=host-sync (host batch input staging:
            # src is the caller's host array, not a device value)
            val = np.asarray(src)
        if val.dtype != np.dtype(bound.dtype):
            val = val.astype(bound.dtype)
        if tuple(val.shape) != tuple(bound.shape):
            raise MXNetError(
                f"fused step: input {name!r} has shape {tuple(val.shape)}, "
                f"bound shape is {tuple(bound.shape)}")
        return jax.device_put(val, self._exe._ctx.jax_device())

    # -- the step ----------------------------------------------------------
    @hot_path
    def step(self, data_batch):
        """Dispatch one fused train step for ``data_batch`` (async)."""
        exe = self._exe
        opt = self._opt
        states = self._updater.states

        # stage batch inputs (device-resident already when the fit loop
        # pre-staged them; host arrays transfer here)
        arrays = {}
        for name, arr in zip(self._data_names, data_batch.data):
            arrays[name] = self._as_device_value(arr, exe.arg_dict[name], name)
        for name, arr in zip(self._label_names, data_batch.label or []):
            if name in exe.arg_dict:
                arrays[name] = self._as_device_value(arr, exe.arg_dict[name],
                                                     name)

        # host-side schedule bookkeeping, identical to the per-param
        # loop: every trainable index counts one update, THEN lr/wd are
        # read (num_update is already advanced for all of them — the
        # same values the per-param loop computes)
        for name in self._trainable:
            if self._indices[name] not in states:
                states[self._indices[name]] = opt.create_state(
                    self._indices[name], exe.arg_dict[name])
            opt._update_count(self._indices[name])
        t = opt.num_update
        lrs = {n: jnp.float32(opt._get_lr(self._indices[n]))
               for n in self._trainable}
        wds = {n: jnp.float32(opt._get_wd(self._indices[n]))
               for n in self._trainable}

        params, others = {}, {}
        trainable = set(self._trainable)
        for name, arr in zip(exe.arg_names, exe.arg_arrays):
            if name in trainable:
                params[name] = arr._data
            elif name in arrays:
                others[name] = arrays[name]
                arr._set(arrays[name])  # keep arg_dict observable state
            else:
                others[name] = arr._data
        aux = {k: a._data for k, a in zip(exe.aux_names, exe.aux_arrays)}
        state_leaves = {n: _state_leaves(states[self._indices[n]])
                        for n in self._trainable}
        key = exe._next_key()

        t_op = jnp.int32(t)
        if not self._aot_resolved:
            self._resolve_aot((params, others, aux, state_leaves, key,
                               lrs, wds, t_op))
        _dispatch_inc(self, "fused_step")
        if self._watch:
            (outs, new_params, new_states, new_aux, outs_ok,
             gnorm) = self._program(params, others, aux, state_leaves,
                                    key, lrs, wds, t_op)
            # ONE batched read for both watchdog scalars — the
            # watchdog's contract is one forced sync per step, not one
            # per scalar (a separate float(gnorm) + bool(outs_ok)
            # would block the dispatch queue twice)
            # mxtpu-lint: disable=host-sync (the watchdog's designed
            # once-per-step sync point)
            ok_h, gn = map(float, jax.device_get((outs_ok, gnorm)))
            from .. import telemetry

            telemetry.gauge("mxtpu_train_grad_norm",
                            "global gradient norm (numeric watchdog)"
                            ).set(gn)
            if not ok_h:
                flight_mod.record_anomaly("fused_step_loss", step=int(t))
            if not np.isfinite(gn):
                flight_mod.record_anomaly("fused_step_grad_norm",
                                          step=int(t))
        else:
            outs, new_params, new_states, new_aux = self._program(
                params, others, aux, state_leaves, key, lrs, wds, t_op)

        # commit: rebind executor arrays to the program's results (no
        # device work — the references move, the buffers stay put)
        for name in self._trainable:
            exe.arg_dict[name]._set(new_params[name])
            _state_commit(states[self._indices[name]], new_states[name])
        for k, arr in zip(exe.aux_names, exe.aux_arrays):
            arr._set(new_aux[k])
        exe._outputs = [NDArray(o, exe._ctx) for o in outs]
        # gradients were consumed inside the program; stale pending
        # state from an earlier unfused run must not survive
        exe._pending_grads = None
        exe._partial = None
        exe._partial_key = None
        return exe._outputs
