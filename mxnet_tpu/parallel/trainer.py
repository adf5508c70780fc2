"""ShardedTrainer: a Symbol's full training step compiled over a mesh.

This is the TPU-native path the reference cannot express: instead of
per-device executors + KVStore push/pull (§3.3/§3.4), the *entire* train
step — forward, backward, gradient all-reduce, optimizer update — is one
jitted XLA program whose inputs carry ``NamedSharding``s.  The GSPMD
partitioner inserts the collectives: batch sharded over ``dp`` yields a
gradient psum over ICI (the dist_sync path collapsed into the step,
SURVEY.md §3.4 "TPU translation"); parameters sharded over ``tp`` yield
tensor-parallel matmul collectives; sequence-sharded activations over
``sp`` yield context parallelism.

The Module/KVStore stack remains the MXNet-compatible surface; this
trainer is the performance path for pod-scale runs.
"""

from __future__ import annotations

import logging
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import random as _random
from .. import telemetry
from ..base import MXNetError, np_dtype
from ..executor import _CompiledGraph
from ..initializer import Uniform
from ..lint.annotations import hot_path
from .. import ndarray as nd

__all__ = ["ShardedTrainer", "sgd_opt", "adam_opt", "adamw_opt",
           "cached_sgd_step"]


def cached_sgd_step(cache, loss_fn, make_objective, has_aux=False):
    """Shared jitted-SGD-step cache for the module wrappers
    (PipelineModule / MoELayer).

    Returns a jitted ``step(params, x, lr, *extra) -> (loss, aux,
    new_params)`` (``aux`` is None unless ``has_aux``) cached per
    ``loss_fn`` OBJECT — never per ``id(loss_fn)``: an id can be
    recycled after GC, handing a brand-new loss_fn another function's
    compiled program (mxtpu-lint's jit-cache-capture rule).  Keying by
    the object keeps the entry correct, and the bounded eviction below
    keeps fresh-lambda call sites from pinning compiled programs (and
    the objective closures they capture) forever.  Callers must still
    pass a stable function object or every call recompiles.

    ``params`` is donated (TPU-only, like every train step in this
    repo): the update reuses the weight buffers in place, so callers
    must rebind — ``…, self.params = step(self.params, …)`` — and never
    read the donated pytree afterwards.  Cross-module analysis cannot
    see this factory's jit, so call sites annotate the binding with
    ``# mxtpu-lint: donates=0`` to put the use-after-donate checker on
    duty there.  ``make_objective(loss_fn, x,
    *extra)`` builds the ``params -> loss`` (or ``params -> (loss,
    aux)`` with ``has_aux``) objective at trace time.
    """
    from ..optimizer import _donate

    key = (loss_fn, has_aux)
    step = cache.get(key)
    if step is None:
        def step_fn(params, x, lr, *extra):
            objective = make_objective(loss_fn, x, *extra)
            if has_aux:
                (loss, aux), grads = jax.value_and_grad(
                    objective, has_aux=True)(params)
            else:
                loss, grads = jax.value_and_grad(objective)(params)
                aux = None
            new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                                params, grads)
            return loss, aux, new_params

        step = jax.jit(step_fn, donate_argnums=_donate(0))
        # bounded like pipeline's _RUN_CACHE: evict oldest first
        while len(cache) >= 64:
            cache.pop(next(iter(cache)))
        cache[key] = step
    return step


def _clip_grads(grads, clip_gradient=None, clip_by_global_norm=None):
    """Gradient clipping shared by the optimizer factories.

    ``clip_gradient`` is the reference's per-element clamp to
    [-c, c] (optimizer.py SGD/Adam ``clip_gradient``); modern
    ``clip_by_global_norm`` rescales the whole pytree when its L2 norm
    exceeds the bound.  Both compute in f32; under a sharded step the
    global-norm sum becomes one scalar psum inserted by the
    partitioner."""
    if clip_gradient is not None:
        c = float(clip_gradient)
        grads = {k: jnp.clip(g.astype(jnp.float32), -c, c)
                 for k, g in grads.items()}
    if clip_by_global_norm is not None:
        c = float(clip_by_global_norm)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in grads.values())
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, c / jnp.maximum(norm, 1e-12))
        grads = {k: g.astype(jnp.float32) * scale for k, g in grads.items()}
    return grads


def sgd_opt(learning_rate=0.01, momentum=0.9, weight_decay=0.0,
            clip_gradient=None, clip_by_global_norm=None,
            state_dtype=None):
    """Functional SGD(+momentum) over a param pytree.

    ``state_dtype`` sets the dtype the momentum buffer is STORED in
    (compute is always f32).  Default: the param dtype — with bf16
    params that halves optimizer-state HBM traffic per step; pass
    ``float32`` for full-precision accumulation (the MLPerf-style
    recipe when params themselves are bf16)."""
    sdt = jnp.dtype(state_dtype) if state_dtype is not None else None

    def init(params):
        if momentum == 0.0:
            return {}
        return {k: jnp.zeros_like(v, dtype=sdt or v.dtype)
                for k, v in params.items()}

    def update(grads, state, params, lr_scale=1.0):
        grads = _clip_grads(grads, clip_gradient, clip_by_global_norm)
        lr = learning_rate * lr_scale
        new_params, new_state = {}, {}
        for k, p in params.items():
            g = grads[k].astype(jnp.float32) + weight_decay * p.astype(jnp.float32)
            if momentum != 0.0:
                m = momentum * state[k].astype(jnp.float32) - lr * g
                new_state[k] = m.astype(sdt or p.dtype)
                new_params[k] = (p.astype(jnp.float32) + m).astype(p.dtype)
            else:
                new_params[k] = (p.astype(jnp.float32) - lr * g).astype(p.dtype)
        return new_params, new_state

    return init, update


def adam_opt(learning_rate=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
             weight_decay=0.0, decoupled=False,
             clip_gradient=None, clip_by_global_norm=None):
    """Functional Adam over a param pytree.

    ``decoupled=True`` gives AdamW: weight decay multiplies the weights
    by (1 - lr*wd) instead of being folded into the gradient."""

    def init(params):
        z = {k: jnp.zeros_like(v, dtype=jnp.float32) for k, v in params.items()}
        return {"m": z, "v": {k: jnp.zeros_like(val, dtype=jnp.float32)
                              for k, val in params.items()},
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr_scale=1.0):
        grads = _clip_grads(grads, clip_gradient, clip_by_global_norm)
        t = state["t"] + 1
        lr_t = (learning_rate * lr_scale
                * jnp.sqrt(1 - beta2**t.astype(jnp.float32))
                / (1 - beta1**t.astype(jnp.float32)))
        new_params, new_m, new_v = {}, {}, {}
        for k, p in params.items():
            pf = p.astype(jnp.float32)
            g = grads[k].astype(jnp.float32)
            if not decoupled:
                g = g + weight_decay * pf
            m = beta1 * state["m"][k] + (1 - beta1) * g
            v = beta2 * state["v"][k] + (1 - beta2) * jnp.square(g)
            new_m[k], new_v[k] = m, v
            if decoupled:
                # decay strength follows the SCHEDULED lr (standard AdamW)
                pf = pf * (1.0 - learning_rate * lr_scale * weight_decay)
            new_params[k] = (pf - lr_t * m
                             / (jnp.sqrt(v) + eps)).astype(p.dtype)
        return new_params, {"m": new_m, "v": new_v, "t": t}

    return init, update


def adamw_opt(learning_rate=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
              weight_decay=0.0, clip_gradient=None,
              clip_by_global_norm=None):
    """Functional AdamW: adam_opt with decoupled weight decay."""
    return adam_opt(learning_rate, beta1, beta2, eps, weight_decay,
                    decoupled=True, clip_gradient=clip_gradient,
                    clip_by_global_norm=clip_by_global_norm)


def lars_opt(learning_rate=0.01, momentum=0.9, weight_decay=0.0,
             trust_coefficient=0.001, eps=1e-9,
             clip_gradient=None, clip_by_global_norm=None):
    """Functional LARS (You et al. 2017) — SGD+momentum with a
    per-layer trust ratio ``eta*||w||/(||g||+wd*||w||)``, the standard
    large-batch ResNet optimizer on TPU pods.  Bias/norm params
    (ndim <= 1) update as plain SGD (standard exclusion)."""

    def init(params):
        return {k: jnp.zeros_like(v, dtype=jnp.float32)
                for k, v in params.items()}

    def update(grads, state, params, lr_scale=1.0):
        grads = _clip_grads(grads, clip_gradient, clip_by_global_norm)
        lr = learning_rate * lr_scale
        new_params, new_state = {}, {}
        for k, p in params.items():
            pf = p.astype(jnp.float32)
            g = grads[k].astype(jnp.float32)
            if p.ndim > 1:
                w_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
                g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
                ratio = jnp.where(
                    (w_norm > 0) & (g_norm > 0),
                    trust_coefficient * w_norm
                    / (g_norm + weight_decay * w_norm + eps), 1.0)
            else:
                ratio = 1.0
            g = g + weight_decay * pf
            m = momentum * state[k] + lr * ratio * g
            new_state[k] = m
            new_params[k] = (pf - m).astype(p.dtype)
        return new_params, new_state

    return init, update


def lamb_opt(learning_rate=1e-3, beta1=0.9, beta2=0.999, eps=1e-6,
             weight_decay=0.0, clip_gradient=None,
             clip_by_global_norm=None):
    """Functional LAMB (You et al. 2019) — Adam moments with a
    per-layer ``||w||/||r||`` rescale of the update direction, the
    large-batch BERT/transformer optimizer.  Bias/norm params skip the
    adaptation."""

    def init(params):
        z = {k: jnp.zeros_like(v, dtype=jnp.float32)
             for k, v in params.items()}
        return {"m": z, "v": {k: jnp.zeros_like(val, dtype=jnp.float32)
                              for k, val in params.items()},
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr_scale=1.0):
        grads = _clip_grads(grads, clip_gradient, clip_by_global_norm)
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        lr = learning_rate * lr_scale
        new_params, new_m, new_v = {}, {}, {}
        for k, p in params.items():
            pf = p.astype(jnp.float32)
            g = grads[k].astype(jnp.float32)
            m = beta1 * state["m"][k] + (1 - beta1) * g
            v = beta2 * state["v"][k] + (1 - beta2) * jnp.square(g)
            new_m[k], new_v[k] = m, v
            m_hat = m / (1 - beta1**tf)
            v_hat = v / (1 - beta2**tf)
            r = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * pf
            if p.ndim > 1:
                w_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
                r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
                ratio = jnp.where((w_norm > 0) & (r_norm > 0),
                                  w_norm / r_norm, 1.0)
            else:
                ratio = 1.0
            new_params[k] = (pf - lr * ratio * r).astype(p.dtype)
        return new_params, {"m": new_m, "v": new_v, "t": t}

    return init, update


_OPTS = {"sgd": sgd_opt, "adam": adam_opt, "adamw": adamw_opt,
         "lars": lars_opt, "lamb": lamb_opt}


class ShardedTrainer:
    """Compile and run a full sharded train step for a Symbol.

    Parameters
    ----------
    symbol : Symbol with a loss head (SoftmaxOutput / MakeLoss / ...)
    input_shapes : dict name -> global shape (batch dim = global batch)
    mesh : jax.sharding.Mesh; axes referenced by batch_axis/param_specs
    batch_axis : mesh axis name data is sharded over (data parallelism)
    param_specs : {param_name_or_regex: PartitionSpec} for tensor/expert
        parallel parameter sharding; unlisted params are replicated
    sequence_specs : {input_name: PartitionSpec} extra input shardings
        (e.g. sequence axis over 'sp' for context parallelism)
    optimizer : 'sgd' | 'adam' | 'adamw' | (init_fn, update_fn)
    dtype : compute dtype for params (bfloat16 recommended on TPU)
    grad_accum_steps : process the global batch as N sequential
        microbatches inside one compiled step (single optimizer update).
        Exact for deterministic graphs; dropout draws per-microbatch RNG
        and BatchNorm sees microbatch statistics (standard caveat)
    shard_optimizer_state : ZeRO-1 — momentum/Adam moments of
        replicated params shard over the data axis, cutting optimizer
        memory by the dp degree; math is unchanged (XLA gathers shards
        where the update needs them)
    fsdp : ZeRO-3 — STORE parameters sharded over the data axis
        (largest dp-divisible dim per param).  XLA all-gathers each
        param where a layer consumes it and reduce-scatters its
        gradient, so per-device param+grad+optimizer memory drops by
        the dp degree while the math is unchanged.  Composes with
        param_specs (explicit specs win, e.g. tensor-parallel layers)
        and grad_accum_steps.  ``fsdp_min_size`` (elements) keeps small
        params replicated — their all-gather latency outweighs the
        bytes saved
    lr_scheduler : ``mx.lr_scheduler.LRScheduler`` (or any
        ``step -> lr`` callable) evaluated on host each step; the value
        enters the compiled step as a traced scalar, so schedules
        (warmup, factor decay, cosine) never trigger recompilation
    """

    def __init__(self, symbol, input_shapes, mesh=None, batch_axis="dp",
                 param_specs=None, sequence_specs=None, optimizer="sgd",
                 optimizer_params=None, initializer=None, dtype="float32",
                 input_dtypes=None, rescale_grad=None, grad_accum_steps=1,
                 shard_optimizer_state=False, lr_scheduler=None,
                 fsdp=False, fsdp_min_size=2 ** 17, seq_axis=None):
        if mesh is None:
            from .mesh import local_mesh

            mesh = local_mesh(batch_axis)
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.symbol = symbol
        self._graph = _CompiledGraph(symbol)
        self.input_names = list(input_shapes)
        self.param_names = [n for n in symbol.list_arguments()
                            if n not in input_shapes]
        self.aux_names = symbol.list_auxiliary_states()
        self._dtype = np_dtype(dtype)

        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**input_shapes)
        arg_types, _, _ = symbol.infer_type(
            **{k: v for k, v in (input_dtypes or {}).items()})
        name2shape = dict(zip(symbol.list_arguments(), arg_shapes))
        name2type = dict(zip(symbol.list_arguments(), arg_types))
        self.out_shapes = out_shapes
        self._input_shapes = dict(input_shapes)
        self._input_dtypes = {k: name2type.get(k) or np.float32
                              for k in self.input_names}
        if input_dtypes:
            self._input_dtypes.update(input_dtypes)
        # mixed precision: float data inputs follow the compute dtype;
        # labels stay f32 (bf16 cannot represent class ids > 256 exactly)
        for k in self.input_names:
            if (self._dtype != np.float32
                    and np.issubdtype(self._input_dtypes[k], np.floating)
                    and not k.endswith("label")):
                self._input_dtypes[k] = self._dtype

        # -- initialize params on host, then place with shardings ----------
        initializer = initializer or Uniform(0.07)

        fsdp_dp = mesh.shape.get(batch_axis, 1) if fsdp else 1

        def fsdp_spec(name, shape):
            """FSDP / ZeRO-3: STORE the param sharded over the data axis
            (largest dp-divisible dim); XLA all-gathers it where a layer
            consumes it and reduce-scatters its gradient — per-device
            param+grad+state memory drops by the dp degree.  Small
            params (< fsdp_min_size elements) stay replicated: their
            all-gather latency outweighs the bytes saved."""
            size = int(np.prod(shape)) if shape else 0
            if fsdp_dp <= 1 or size < fsdp_min_size:
                return PartitionSpec()
            dims = [d for d in range(len(shape)) if shape[d] % fsdp_dp == 0]
            if not dims:
                return PartitionSpec()
            dim = max(dims, key=lambda d: shape[d])
            spec = [None] * len(shape)
            spec[dim] = batch_axis
            return PartitionSpec(*spec)

        # param_specs resolve through the shared regex-rule partitioner
        # (parallel/partition.py — the same matcher serve.Engine shards
        # with); dict order is rule priority, mode="full" keeps the
        # historical exact-name-or-fullmatch key contract, and the FSDP
        # heuristic remains the fallback for unmatched params
        from .partition import match_partition_rules

        param_spec_tree = match_partition_rules(
            (param_specs or {}).items(),
            {n: name2shape[n] for n in self.param_names},
            default=fsdp_spec, mode="full")
        self.param_shardings = {n: NamedSharding(mesh, param_spec_tree[n])
                                for n in self.param_names}
        self._replicated = NamedSharding(mesh, PartitionSpec())

        params = {}
        for name in self.param_names:
            host = nd.zeros(name2shape[name], dtype=np.float32)
            initializer(name, host)
            params[name] = jax.device_put(
                host.asnumpy().astype(self._dtype), self.param_shardings[name])
        self.params = params
        aux = {}
        for name, shp in zip(self.aux_names, aux_shapes):
            host = nd.zeros(shp, dtype=np.float32)
            initializer(name, host)
            aux[name] = jax.device_put(host.asnumpy(), self._replicated)
        self.aux = aux

        # -- optimizer ------------------------------------------------------
        import inspect

        if isinstance(optimizer, str):
            opt_factory = _OPTS[optimizer]
            init_fn, update_fn = opt_factory(**(optimizer_params or {}))
            # the scale denominator must be the optimizer's REAL base lr,
            # including each factory's own default (sgd 0.01, adam 1e-3)
            factory_default = inspect.signature(
                opt_factory).parameters["learning_rate"].default
            base_lr = float((optimizer_params or {}).get(
                "learning_rate", factory_default))
        else:
            init_fn, update_fn = optimizer
            base_lr = float((optimizer_params or {}).get(
                "learning_rate", 1.0))
            try:
                sig = inspect.signature(update_fn)
            except (TypeError, ValueError):
                sig = None  # non-introspectable (C extension etc.)
            if sig is not None:
                # the schedule hook must actually be NAMED lr_scale (or
                # absorbed by **kwargs) — a probe that only checks arity
                # would feed the traced multiplier into an unrelated
                # parameter (clip etc.); the call site passes it by
                # keyword for the same reason
                try:
                    sig.bind(None, None, None, lr_scale=1.0)
                    has_scale = True
                except TypeError:
                    has_scale = False
            else:
                has_scale = lr_scheduler is not None
            if not has_scale:
                # custom optimizers predating lr scaling cannot honor a
                # schedule — refuse rather than silently train flat
                if lr_scheduler is not None:
                    raise MXNetError(
                        "lr_scheduler requires the custom optimizer's "
                        "update(grads, state, params, lr_scale) to accept "
                        "an 'lr_scale' argument") from None
                _inner_update = update_fn
                try:  # 4-positional-arg legacy form: feed a constant 1.0
                    sig.bind(None, None, None, 1.0)
                    update_fn = (lambda grads, state, params, lr_scale=1.0:
                                 _inner_update(grads, state, params, 1.0))
                except TypeError:
                    update_fn = (lambda grads, state, params, lr_scale=1.0:
                                 _inner_update(grads, state, params))
        self._lr_scheduler = lr_scheduler
        if lr_scheduler is not None and hasattr(lr_scheduler, "base_lr"):
            # the reference optimizer wiring (optimizer.py:43-45): the
            # scheduler's base lr IS the optimizer's lr
            lr_scheduler.base_lr = base_lr
        self._base_lr = base_lr
        self._num_update = 0
        # param-shaped state (momentum etc.) inherits the param shardings
        # through zeros_like; scalar/odd-shaped leaves (Adam's step count)
        # must be pinned to the mesh explicitly or multi-device jit sees
        # mixed device sets
        # ZeRO-1: momentum/Adam moments of REPLICATED params shard over
        # the data axis (each dp rank owns 1/dp of the state; XLA
        # inserts the gather when the update combines sharded state with
        # replicated params) — optimizer memory drops by the dp degree
        dp_size = mesh.shape.get(batch_axis, 1)
        # built lazily: meshes without a batch axis (pure tp/sp setups)
        # must not fail NamedSharding validation when ZeRO is off
        zero_sharding = (NamedSharding(mesh, PartitionSpec(batch_axis))
                         if shard_optimizer_state
                         and batch_axis in mesh.shape else None)

        def _place_state(leaf):
            sh = getattr(leaf, "sharding", None)
            param_sharded = (isinstance(sh, NamedSharding)
                             and sh.mesh == mesh
                             and sh.spec != PartitionSpec())
            if param_sharded:
                return leaf  # tensor-parallel state follows its param
            if (zero_sharding is not None
                    and getattr(leaf, "ndim", 0) >= 1
                    and leaf.shape[0] % dp_size == 0):
                return jax.device_put(leaf, zero_sharding)
            if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                return leaf
            return jax.device_put(leaf, self._replicated)

        self.opt_state = jax.tree_util.tree_map(_place_state, init_fn(params))
        self._update_fn = update_fn

        # Loss-layer backward is un-normalized (reference SoftmaxOutput
        # contract); like Module.init_optimizer, default rescale to
        # 1/global_batch.
        if rescale_grad is None:
            rescale_grad = 1.0 / next(iter(input_shapes.values()))[0]
        self._rescale_grad = rescale_grad
        # gradient accumulation: the global batch is processed as
        # grad_accum_steps sequential microbatches inside ONE compiled
        # step (lax.scan), with a single optimizer update — activation
        # memory scales with the microbatch, so models whose activations
        # exceed HBM at the full batch still train
        self._accum = int(grad_accum_steps)
        if self._accum > 1:
            for name, shp in input_shapes.items():
                if shp[0] % self._accum:
                    raise ValueError(
                        f"batch dim of {name!r} ({shp[0]}) must be "
                        f"divisible by grad_accum_steps ({self._accum})")

        self.batch_shardings = {
            n: NamedSharding(mesh, (sequence_specs or {}).get(
                n, PartitionSpec(batch_axis)))
            for n in self.input_names}
        # the sequence-parallel mesh axis: FlashAttention ops in the
        # graph route to ring attention over it — per-shard local
        # attention over a sharded sequence would be silently wrong.
        # Explicit ``seq_axis=`` wins; otherwise inferred as the one
        # non-batch axis sequence_specs shard over, and AMBIGUOUS specs
        # raise rather than silently disabling the routing (which would
        # make GSPMD all-gather the sequence at every attention).
        if seq_axis is not None:
            self._attn_seq_axis = seq_axis
        else:
            seq_axes = set()
            for spec in (sequence_specs or {}).values():
                for entry in spec:
                    for nm in (entry if isinstance(entry, (tuple, list))
                               else (entry,)):
                        if nm is not None and nm != batch_axis:
                            seq_axes.add(nm)
            if len(seq_axes) > 1:
                raise ValueError(
                    f"sequence_specs shard over multiple non-batch axes "
                    f"{sorted(seq_axes)}; pass seq_axis= to name the "
                    "sequence-parallel axis explicitly")
            self._attn_seq_axis = seq_axes.pop() if seq_axes else None
        # placed like every later key (the step returns it replicated): an
        # uncommitted first key gives step 2 a different input sharding
        # than step 1, and jit compiles the whole train step a second time
        self._key = jax.device_put(_random.next_key(), self._replicated)
        # telemetry handles (no-op objects when disabled).  step time is
        # HOST time around the jitted call — dispatch cost when XLA runs
        # async, the full device step when the result is consumed
        self._tel_steps = telemetry.counter(
            "mxtpu_trainer_steps_total", "ShardedTrainer optimizer steps")
        self._tel_step_secs = telemetry.histogram(
            "mxtpu_trainer_step_seconds",
            "host wall time per train_step dispatch")
        self._tel_data_wait = telemetry.histogram(
            "mxtpu_trainer_data_wait_seconds",
            "fit() wait on the host->device staging queue")
        self._build_steps()

    # ------------------------------------------------------------------ #
    def _build_steps(self):
        from ..ops.attention import spmd_attention

        graph = self._graph

        n_accum = self._accum
        mesh, batch_axis = self.mesh, self.batch_axis
        seq_axis = self._attn_seq_axis

        def grads_of(params, aux, batch, sub):
            def f(p):
                # ambient mesh for fused-attention ops: their Mosaic
                # kernels must shard_map over the batch axis inside a
                # multi-device program (GSPMD can't partition them), and
                # a sharded sequence axis routes them to ring attention
                with spmd_attention(mesh, batch_axis, seq_axis):
                    outs, new_aux = graph({**p, **batch}, aux, sub, True)
                return outs, new_aux

            outs, vjp_fn, new_aux = jax.vjp(f, params, has_aux=True)
            head = tuple(jnp.ones_like(o) for o in outs)
            return vjp_fn(head)[0], new_aux, outs

        def train_step(params, opt_state, aux, batch, key, lr_scale):
            # split inside the step: the whole key chain lives on-device,
            # so each step is ONE program dispatch (a separate host-side
            # split program adds a dispatch gap per step)
            key, sub = jax.random.split(key)
            if n_accum == 1:
                grads, new_aux, outs = grads_of(params, aux, batch, sub)
            else:
                # pin each microbatch's own batch dim to the original
                # input sharding (accum axis replicated) — otherwise the
                # partitioner may shard the scan axis and insert
                # per-microbatch collectives
                micro = {
                    k: jax.lax.with_sharding_constraint(
                        v.reshape((n_accum, v.shape[0] // n_accum)
                                  + v.shape[1:]),
                        NamedSharding(self.mesh, PartitionSpec(
                            None, *self.batch_shardings[k].spec)))
                    for k, v in batch.items()}

                def body(carry, mb):
                    g_acc, aux_c, key_c = carry
                    key_c, s = jax.random.split(key_c)
                    g, aux_n, outs_mb = grads_of(params, aux_c, mb, s)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, aux_n, key_c), outs_mb

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, new_aux, sub), outs_st = jax.lax.scan(
                    body, (zeros, aux, sub), micro)
                # microbatch outputs stacked on a leading accum axis.
                # ASSUMPTION: head outputs are per-sample batch-leading
                # (the SoftmaxOutput/MakeLoss contract this trainer
                # targets) or scalar.  Batch-leading outputs flatten back
                # to the global batch for metrics; scalar heads combine
                # by SUM — consistent with the un-normalized loss
                # contract (rescale_grad=1/global_batch assumes
                # sum-losses); a mean-reduced head will read differently
                # across accumulation settings.
                outs = tuple(
                    jnp.sum(o, axis=0) if o.ndim == 1
                    else o.reshape((-1,) + o.shape[2:])
                    for o in outs_st)
            scale = self._rescale_grad
            grads = {k: g * scale for k, g in grads.items()}
            new_params, new_opt = self._update_fn(grads, opt_state, params,
                                                  lr_scale=lr_scale)
            return new_params, new_opt, new_aux, outs, key

        def eval_step(params, aux, batch, key):
            with spmd_attention(mesh, batch_axis, seq_axis):
                outs, _ = graph({**params, **batch}, aux, key, False)
            return outs

        p_shard = self.param_shardings
        rep = self._replicated
        opt_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, self.opt_state)
        aux_shardings = {k: rep for k in self.aux_names}
        self._train_step = jax.jit(
            train_step,
            in_shardings=(p_shard, opt_shardings, aux_shardings,
                          self.batch_shardings, rep, rep),
            out_shardings=(p_shard, opt_shardings, aux_shardings, None, rep),
            donate_argnums=(0, 1, 2),
        )
        self._eval_step = jax.jit(
            eval_step,
            in_shardings=(p_shard, aux_shardings, self.batch_shardings, rep),
        )

    def _place_batch(self, batch):
        placed = {}
        for name in self.input_names:
            v = batch[name]
            if isinstance(v, nd.NDArray):
                # mxtpu-lint: disable=host-sync (host batch ingestion —
                # the input pipeline hands over host arrays here)
                v = v.asnumpy()
            # mxtpu-lint: disable=host-sync (host batch ingestion)
            v = np.asarray(v, dtype=self._input_dtypes[name])
            placed[name] = jax.device_put(v, self.batch_shardings[name])
        return placed

    def _lr_scale(self):
        """Host-side schedule evaluation -> traced scalar multiplier."""
        self._num_update += 1
        if self._lr_scheduler is None:
            return np.float32(1.0)
        # mxtpu-lint: disable=host-sync (host-side Python schedule —
        # no device value ever flows through the lr scheduler)
        lr = float(self._lr_scheduler(self._num_update))
        return np.float32(lr / max(self._base_lr, 1e-30))

    @hot_path
    def step(self, batch: dict):
        """One optimizer step on a global batch; returns outputs."""
        t0 = time.perf_counter()
        with telemetry.span("trainer.step"):
            placed = self._place_batch(batch)
            self.params, self.opt_state, self.aux, outs, self._key = \
                self._train_step(self.params, self.opt_state, self.aux,
                                 placed, self._key, self._lr_scale())
        self._tel_step_secs.observe(time.perf_counter() - t0)
        self._tel_steps.inc()
        return outs

    def eval(self, batch: dict):
        self._key, sub = jax.random.split(self._key)
        return self._eval_step(self.params, self.aux, self._place_batch(batch), sub)

    def get_params(self):
        """Gather params to host as name->np.ndarray (checkpoint surface)."""
        return {k: np.asarray(jax.device_get(v)) for k, v in self.params.items()}

    def set_params(self, arg_params):
        for k, v in arg_params.items():
            if k in self.params:
                self.params[k] = jax.device_put(
                    np.asarray(v).astype(self._dtype), self.param_shardings[k])

    # ------------------------------------------------------------------ #
    # training-loop conveniences (FeedForward.fit surface at trainer
    # level, with TPU-style host/device overlap)

    def fit(self, train_iter, num_epochs=1, eval_metric=None,
            batch_end_callback=None, epoch_end_callback=None):
        """Epoch loop with double-buffered host->device staging: batch
        n+1 is placed (host copy + transfer) on a prefetch thread while
        step n's XLA program runs — the trainer-level analog of the
        reference's PrefetchingIter + async engine overlap
        (io/iter_prefetcher.h; python/mxnet/model.py:87-115)."""
        import queue
        import threading

        from .. import ndarray as _nd
        from ..metric import create as metric_create

        metric = (metric_create(eval_metric)
                  if isinstance(eval_metric, str) else eval_metric)
        for epoch in range(num_epochs):
            train_iter.reset()
            if metric is not None:
                metric.reset()
            q = queue.Queue(maxsize=2)

            def produce():
                try:
                    for batch in train_iter:
                        feed = {}
                        for desc, arr in zip(train_iter.provide_data,
                                             batch.data):
                            feed[desc[0]] = arr
                        for desc, arr in zip(train_iter.provide_label or [],
                                             batch.label):
                            feed[desc[0]] = arr
                        # place on device from the prefetch thread: the
                        # transfer overlaps the in-flight training step
                        q.put((self._place_batch(feed), batch.label))
                    q.put(None)
                except BaseException as e:  # surface in the consumer
                    q.put(e)

            t = threading.Thread(target=produce, daemon=True)
            t.start()
            nbatch = 0
            while True:
                t0 = time.perf_counter()
                with telemetry.span("trainer.data_wait"):
                    item = q.get()
                self._tel_data_wait.observe(time.perf_counter() - t0)
                if item is None:
                    break
                if isinstance(item, BaseException):
                    t.join()
                    raise item
                placed, labels = item
                t0 = time.perf_counter()
                with telemetry.span("trainer.step"):
                    self.params, self.opt_state, self.aux, outs, self._key = \
                        self._train_step(self.params, self.opt_state,
                                         self.aux, placed, self._key,
                                         self._lr_scale())
                self._tel_step_secs.observe(time.perf_counter() - t0)
                self._tel_steps.inc()
                nbatch += 1
                if metric is not None and labels:
                    # host sync happens only when metrics are requested
                    metric.update(labels,
                                  [_nd.NDArray(o) for o in outs[:1]])
                if batch_end_callback is not None:
                    batch_end_callback(epoch, nbatch, metric)
            t.join()
            if epoch_end_callback is not None:
                epoch_end_callback(epoch, self)
        return metric

    # -- checkpointing ------------------------------------------------------
    def save_checkpoint(self, prefix, epoch=0, async_save=False):
        """Two-artifact checkpoint (reference model.save contract:
        symbol JSON + params blob) plus the optimizer state + RNG key,
        so a sharded run resumes exactly.

        ``async_save=True`` gives orbax-style semantics: the
        device->host snapshot happens now (later steps cannot corrupt
        it); serialization + file IO run on background writers with
        atomic temp-file renames (shared machinery with
        ``model.save_checkpoint``).  Call :meth:`wait_checkpoints` (or
        ``mx.model.wait_checkpoints()``) before relying on the files."""
        import pickle

        from .. import model as model_mod

        # plain-numpy snapshot: nd.save serializes numpy directly, so no
        # host->device->host round-trip for large param sets
        arg_params = self.get_params()
        aux_params = {k: np.asarray(jax.device_get(v))
                      for k, v in self.aux.items()}
        model_mod.save_checkpoint(prefix, epoch, self.symbol, arg_params,
                                  aux_params, async_save=async_save,
                                  snapshot_owned=True)
        opt_host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), self.opt_state)
        # the RNG key is part of exact-resume state: dropout chains must
        # continue where the interrupted run left off
        sched_state = None
        if self._lr_scheduler is not None:
            try:
                sched_state = pickle.dumps(self._lr_scheduler)
            except Exception:
                sched_state = None  # unpicklable custom callable
                logging.warning(
                    "lr_scheduler %r is not picklable; checkpoint will "
                    "not carry scheduler state and a resumed run keeps "
                    "the live scheduler object as-is",
                    type(self._lr_scheduler).__name__)
        blob = pickle.dumps({"opt_state": opt_host,
                             "rng_key": np.asarray(jax.device_get(self._key)),
                             "num_update": self._num_update,
                             "lr_scheduler": sched_state})
        states_name = f"{prefix}-{epoch:04d}.states"

        def write_states(path):
            with open(path, "wb") as f:
                f.write(blob)

        if async_save:
            model_mod.stage_async_write(states_name, write_states)
        else:
            write_states(states_name)

    def wait_checkpoints(self):
        """Block until in-flight async checkpoint writes are on disk,
        surfacing any write failure (per-file attribution)."""
        from .. import model as model_mod

        model_mod.wait_checkpoints()

    def load_checkpoint(self, prefix, epoch=0):
        """Restore params, aux and optimizer state with the trainer's
        shardings re-applied."""
        import pickle

        from .. import ndarray as nd

        loaded = nd.load(f"{prefix}-{epoch:04d}.params")
        self.set_params({k[4:]: v.asnumpy() for k, v in loaded.items()
                         if k.startswith("arg:")})
        for k, v in loaded.items():
            if k.startswith("aux:") and k[4:] in self.aux:
                self.aux[k[4:]] = jax.device_put(v.asnumpy(),
                                                 self._replicated)
        with open(f"{prefix}-{epoch:04d}.states", "rb") as f:
            blob = pickle.loads(f.read())
        opt_host = blob["opt_state"] if isinstance(blob, dict) else blob
        self.opt_state = jax.tree_util.tree_map(
            lambda host, cur: jax.device_put(
                np.asarray(host).astype(cur.dtype), cur.sharding),
            opt_host, self.opt_state)
        if isinstance(blob, dict) and "rng_key" in blob:
            self._key = jax.device_put(blob["rng_key"], self._replicated)
        if isinstance(blob, dict):
            self._num_update = int(blob.get("num_update", self._num_update))
            if (blob.get("lr_scheduler") is not None
                    and self._lr_scheduler is not None):
                # stateful schedulers (factor counters) rewind with the
                # checkpoint; without this an earlier checkpoint would
                # resume at a permanently-decayed lr.  Guarded on the
                # trainer HAVING a scheduler: a trainer built with
                # lr_scheduler=None (constant-lr fine-tune) must not
                # silently inherit the checkpointed schedule
                self._lr_scheduler = pickle.loads(blob["lr_scheduler"])

    # -- sharded (per-host) checkpointing -----------------------------------
    def save_checkpoint_sharded(self, ckpt_dir, epoch=0, async_save=False):
        """Pod-scale checkpoint: every process writes only its local
        shards (peak host memory = largest local shard, multi-host saves
        are parallel), via :mod:`mxnet_tpu.parallel.checkpoint`.  The
        dense two-artifact path (:meth:`save_checkpoint`) stays the
        portable/interop format; this one is for state that should never
        be gathered.  Restore may use a different mesh/sharding."""
        import base64
        import pickle

        from . import checkpoint as ckpt

        step_dir = os.path.join(ckpt_dir, f"step-{epoch:04d}")
        extra = {"num_update": self._num_update, "epoch": int(epoch)}
        if self._lr_scheduler is not None:
            try:
                extra["lr_scheduler"] = base64.b64encode(
                    pickle.dumps(self._lr_scheduler)).decode("ascii")
            except Exception:
                logging.warning(
                    "lr_scheduler %r is not picklable; sharded checkpoint "
                    "will not carry scheduler state",
                    type(self._lr_scheduler).__name__)
        ckpt.save_sharded(step_dir, self._ckpt_tree(), extra=extra,
                          async_save=async_save)
        if jax.process_index() == 0:
            self.symbol.save(os.path.join(step_dir, "symbol.json"))

    def load_checkpoint_sharded(self, ckpt_dir, epoch=0):
        """Restore a :meth:`save_checkpoint_sharded` checkpoint into this
        trainer's own layout (resharding from the saved layout as
        needed)."""
        import base64
        import pickle

        from . import checkpoint as ckpt

        step_dir = os.path.join(ckpt_dir, f"step-{epoch:04d}")
        state, extra = ckpt.load_sharded(step_dir, self._ckpt_tree())
        self.params = state["params"]
        self.opt_state = state["opt_state"]
        self.aux = state["aux"]
        self._key = state["rng_key"]
        if extra:
            self._num_update = int(extra.get("num_update",
                                             self._num_update))
            if (extra.get("lr_scheduler") is not None
                    and self._lr_scheduler is not None):
                self._lr_scheduler = pickle.loads(
                    base64.b64decode(extra["lr_scheduler"]))

    def _ckpt_tree(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "aux": self.aux, "rng_key": self._key}
