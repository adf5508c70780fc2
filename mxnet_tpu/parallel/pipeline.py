"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference had no explicit pipeline schedule — overlap emerged from
the dependency engine running different layers' ops on different devices
(SURVEY.md §2.4 "Pipeline parallelism: implicit only").  This module is
the explicit TPU-native upgrade: each device on the ``pp`` mesh axis owns
one stage's parameters; microbatches stream through the ring via
``ppermute`` (ICI neighbor transfers) with a ``lax.scan`` over schedule
ticks, so the whole pipeline — including the bubble — is one compiled
XLA program, differentiable end to end (reverse-mode replays the
schedule backwards).

Requirements: homogeneous stages (same activation shape in/out), stage
parameters stacked on a leading axis sharded over ``pp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["pipeline_apply", "PipelineModule"]


# (stage_fn, mesh, axis, flat specs, treedef, feed/out specs) -> jitted run.
# jax.jit caches on function identity, so the shard_map must be built once
# per config or every call recompiles; specs form pytrees (unhashable by
# lru_cache), hence the explicit dict.
_RUN_CACHE: dict = {}


def _build_pipeline_run(stage_fn, mesh: Mesh, axis: str, param_specs=None,
                        feed_spec=None, out_spec=None):
    """Compiled pipeline program, optionally composed with other mesh
    axes: ``param_specs`` (pytree of PartitionSpec, leading dim = stage
    axis) lets stage weights shard over e.g. ``tp``; ``feed_spec`` /
    ``out_spec`` shard the microbatch feed (e.g. batch over ``dp``).
    The stage_fn is then free to use explicit collectives
    (``lax.psum(..., 'tp')``) — megatron-inside-GPipe composition."""
    rep = PartitionSpec()
    if feed_spec is None:
        feed_spec = rep
    if out_spec is None:
        out_spec = feed_spec
    if param_specs is None:
        p_spec = None
        key_specs = None
    else:
        flat, treedef = jax.tree_util.tree_flatten(param_specs)
        p_spec = param_specs
        key_specs = (tuple(flat), treedef)
    key = (stage_fn, mesh, axis, key_specs, feed_spec, out_spec)
    if key in _RUN_CACHE:
        return _RUN_CACHE[key]
    # bounded like the lru_cache it replaced: fresh stage_fn lambdas at
    # call sites would otherwise pin compiled programs forever
    while len(_RUN_CACHE) >= 64:
        _RUN_CACHE.pop(next(iter(_RUN_CACHE)))

    n_stages = mesh.shape[axis]

    def shard_fn(params, feed_local):
        # params: this device's stage slice, leading dim 1
        params_i = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1

        def tick(carry, feed_t):
            state, ys = carry
            inp = jnp.where(is_first, feed_t, state)
            out = stage_fn(params_i, inp)
            # shift to the next stage; last stage's send wraps but is unused
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state_next = lax.ppermute(out, axis, perm)
            return (state_next, out), out

        state0 = jnp.zeros_like(feed_local[0])
        ys0 = jnp.zeros_like(feed_local[0])
        (_, _), outs = lax.scan(tick, (state0, ys0), feed_local)
        # last stage's outputs for ticks [n_stages-1, total) are the results
        result = outs[n_stages - 1:]
        # replicate the last stage's result to every device
        result = lax.psum(jnp.where(is_last, result, jnp.zeros_like(result)),
                          axis)
        return result

    @jax.jit
    def run(stacked_params, feed):
        if p_spec is None:
            spec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis),
                                          stacked_params)
        else:
            spec = p_spec
        return jax.shard_map(shard_fn, mesh=mesh,
                             in_specs=(spec, feed_spec),
                             out_specs=out_spec,
                             check_vma=False)(stacked_params, feed)

    _RUN_CACHE[key] = run
    return run


def pipeline_apply(stage_fn, stacked_params, x, n_microbatches, mesh: Mesh,
                   axis: str = "pp", param_specs=None, feed_spec=None,
                   out_spec=None):
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` as a pipeline.

    Parameters
    ----------
    stage_fn : (params_i, activation) -> activation, same shape in/out;
        must be a stable function object for compile caching
    stacked_params : pytree whose leaves have leading dim n_stages
        (sharded over ``axis``; each device sees its own stage's slice)
    x : (batch, ...) global input; split into n_microbatches along batch
    n_microbatches : must divide batch
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError("batch not divisible by n_microbatches")
    mb = B // n_microbatches
    xs = x.reshape((n_microbatches, mb) + x.shape[1:])
    pad = jnp.zeros((n_stages - 1,) + xs.shape[1:], xs.dtype)
    feed = jnp.concatenate([xs, pad], axis=0)  # one injection per tick

    run = _build_pipeline_run(stage_fn, mesh, axis, param_specs, feed_spec,
                              out_spec)
    outs = run(stacked_params, feed)
    return outs.reshape((B,) + x.shape[1:])


class PipelineModule:
    """Convenience wrapper: N identical stages + heads, trainable.

    ``stage_fn(params_i, x) -> x`` applied pipeline-parallel, with a
    user ``loss_fn(final_activation, labels) -> scalar`` for training.
    """

    def __init__(self, stage_fn, stacked_params, mesh, axis="pp",
                 n_microbatches=4, param_specs=None, feed_spec=None,
                 out_spec=None):
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.axis = axis
        self.n_microbatches = n_microbatches
        self.param_specs = param_specs
        self.feed_spec = feed_spec
        self.out_spec = out_spec
        self._steps = {}               # (loss_fn id) -> jitted update
        if param_specs is None:
            spec = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, PartitionSpec(axis)),
                stacked_params)
        else:
            spec = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), param_specs)
        self.params = jax.device_put(stacked_params, spec)

    def forward(self, x):
        return pipeline_apply(self.stage_fn, self.params, x,
                              self.n_microbatches, self.mesh, self.axis,
                              self.param_specs, self.feed_spec, self.out_spec)

    def _make_objective(self, loss_fn, x):
        def objective(params):
            out = pipeline_apply(self.stage_fn, params, x,
                                 self.n_microbatches, self.mesh, self.axis,
                                 self.param_specs, self.feed_spec,
                                 self.out_spec)
            return loss_fn(out)

        return objective

    def grad_step(self, x, loss_fn, lr=0.01):
        """One SGD step through the pipelined computation.

        ``loss_fn`` must be a stable function object — the jitted update
        is cached per loss_fn, so a fresh lambda per call recompiles."""
        from .trainer import cached_sgd_step

        # mxtpu-lint: donates=0 (params buffers reused in place on TPU)
        step = cached_sgd_step(self._steps, loss_fn, self._make_objective)
        loss, _, self.params = step(self.params, x, lr)
        return loss
