"""Mixture-of-Experts with expert parallelism (``ep`` mesh axis).

New first-class TPU capability (absent in the reference — SURVEY.md §2.4
marks expert parallelism "No").  Implements Switch/top-k token routing
with capacity-based dispatch: each device on the ``ep`` axis owns
``E / n_shards`` experts; tokens are routed with an in-program
``lax.all_to_all`` over ICI (dispatch), run through the local experts,
and routed back (combine), all inside one ``shard_map``-compiled XLA
program so the router, both all-to-alls, the expert FFNs, and the
load-balancing auxiliary loss fuse into a single differentiable step.

Dispatch math follows the standard capacity formulation (Switch
Transformer / GShard): per-expert capacity ``C = ceil(k * tokens_per
_shard / E * capacity_factor)``; tokens beyond capacity are dropped from
that expert (their combine weight is zero, so the layer degrades to the
residual path if the caller adds one).

Exposed as:
- ``moe_apply(...)`` — functional sharded call (differentiable);
- ``moe_reference(...)`` — identical math, single device, for tests;
- ``MoELayer`` — stateful convenience wrapper (init + trainable step).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["moe_apply", "moe_reference", "MoELayer", "init_moe_params"]


def _router(x, gate_w, num_experts, k, capacity):
    """Token routing: returns (dispatch, combine, aux_loss).

    x: (T, D) tokens.  dispatch: (T, E, C) one-hot routing tensor;
    combine: same shape scaled by gate probabilities.
    """
    T = x.shape[0]
    # all routing math in float32: a bf16 cumsum is inexact past 256 and
    # would silently assign duplicate capacity slots
    logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((T, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((T, num_experts, capacity), jnp.float32)
    masked = probs
    # occupancy per expert carried across the k routing rounds
    occupancy = jnp.zeros((num_experts,), jnp.int32)
    frac_routed = jnp.zeros((num_experts,), jnp.float32)
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                 # (T,)
        gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)  # (T, E)
        # position of each token within its expert's buffer this round
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) + occupancy[None, :].astype(
            jnp.float32)
        pos_int = pos.astype(jnp.int32)
        keep = (pos_int < capacity).astype(jnp.float32) * onehot
        slot = jax.nn.one_hot(pos_int, capacity, dtype=jnp.float32)  # (T,E,C)
        d = keep[..., None] * slot
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        frac_routed = frac_routed + jnp.sum(onehot, axis=0) / T
        occupancy = occupancy + jnp.sum(keep, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)                  # exclude chosen

    # Switch-style load-balancing loss: E * <frac tokens> . <mean prob>
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum((frac_routed / k) * mean_prob)
    return dispatch.astype(x.dtype), combine.astype(x.dtype), aux_loss


def _expert_ffn(params_i, h):
    """One expert: two-layer FFN with ReLU (params: w1, b1, w2, b2)."""
    h = jnp.maximum(h @ params_i["w1"] + params_i["b1"], 0.0)
    return h @ params_i["w2"] + params_i["b2"]


def capacity_for(tokens_per_shard, num_experts, k=1, capacity_factor=1.25):
    return max(1, int(math.ceil(k * tokens_per_shard / num_experts
                                * capacity_factor)))


@functools.lru_cache(maxsize=64)
def _build_moe_run(mesh: Mesh, axis: str, k: int, E: int, C: int, expert_fn,
                   batch_axis=None):
    """Cached compiled MoE step for one (mesh, routing config) combo.

    jax.jit caches on function identity + input shapes, so the shard_map
    program must be built once per config, not per call — otherwise every
    training step recompiles.

    ``batch_axis``: optional data-parallel mesh axis the token dim is
    ALSO sharded over.  Each dp replica routes its own tokens among its
    ep group (the all-to-alls stay inside the ep axis, riding ICI), so
    expert parallelism composes with data parallelism in one mesh.
    """
    n_shards = mesh.shape[axis]
    epl = E // n_shards            # experts per shard
    tok_dims = (batch_axis, axis) if batch_axis else axis
    tok_spec = PartitionSpec(tok_dims, None)
    gate_spec = PartitionSpec(None, None)

    def shard_fn(gate_w, experts_local, x_local):
        dispatch, combine, aux = _router(x_local, gate_w, E, k, C)
        # gather each expert's token buffer: (E, C, D)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x_local)
        D = expert_in.shape[-1]
        # dispatch all-to-all: device g receives, from every shard s, the
        # buffers for its expert group -> (n_shards, epl, C, D)
        expert_in = expert_in.reshape(n_shards, epl, C, D)
        expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                                   concat_axis=0, tiled=False)
        # run local experts over all shards' tokens at once
        flat_in = expert_in.transpose(1, 0, 2, 3).reshape(epl, n_shards * C, D)
        flat_out = jax.vmap(expert_fn)(experts_local, flat_in)
        Do = flat_out.shape[-1]
        # combine all-to-all: route results back to their source shards
        out = flat_out.reshape(epl, n_shards, C, Do).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                             tiled=False)
        out = out.reshape(E, C, Do)
        y_local = jnp.einsum("tec,ecd->td", combine, out)
        # aux loss: average over shards so the global loss is one scalar
        aux = lax.pmean(aux, axis)
        if batch_axis:
            aux = lax.pmean(aux, batch_axis)
        return y_local, aux

    @jax.jit
    def run(gate_w, experts, x):
        exp_spec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis),
                                          experts)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(gate_spec, exp_spec, tok_spec),
            out_specs=(tok_spec, PartitionSpec()),
            check_vma=False)(gate_w, experts, x)

    return run


def moe_apply(params, x, mesh: Mesh, axis: str = "ep", k: int = 1,
              capacity_factor: float = 1.25, expert_fn=_expert_ffn,
              batch_axis=None):
    """Expert-parallel MoE layer over mesh axis ``axis``.

    Parameters
    ----------
    params : dict with "gate_w" (D, E) replicated and "experts", a pytree
        whose leaves have leading dim E (sharded over ``axis``).
    x : (tokens, D) global batch of tokens, sharded over ``axis`` on dim 0
        (replicated input is placed here).
    expert_fn : must be a stable function object — compiled programs are
        cached per (mesh, routing config, expert_fn); a fresh lambda per
        call recompiles and churns the cache.
    batch_axis : optional dp mesh axis the token dim is additionally
        sharded over (dp-major ordering); expert params stay replicated
        across it and each dp replica's ep group routes independently.
        Per-shard capacity then uses tokens / (dp * ep).
    Returns (y, aux_loss) with y sharded like x.
    """
    n_shards = mesh.shape[axis]
    E = params["gate_w"].shape[1]
    if E % n_shards:
        raise ValueError(f"num_experts {E} not divisible by ep={n_shards}")
    if batch_axis is not None:
        if batch_axis == axis:
            raise ValueError(
                f"batch_axis must differ from the expert axis ({axis!r})")
        if batch_axis not in mesh.shape:
            raise ValueError(
                f"batch_axis {batch_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}")
    n_tok_shards = n_shards * (mesh.shape[batch_axis] if batch_axis else 1)
    T = x.shape[0]
    if T % n_tok_shards:
        raise ValueError(
            f"tokens {T} not divisible by token shards {n_tok_shards}")
    C = capacity_for(T // n_tok_shards, E, k, capacity_factor)
    run = _build_moe_run(mesh, axis, k, E, C, expert_fn, batch_axis)

    if not isinstance(x, jax.core.Tracer):
        tok_dims = (batch_axis, axis) if batch_axis else axis
        x = jax.device_put(x,
                           NamedSharding(mesh, PartitionSpec(tok_dims, None)))
    return run(params["gate_w"], params["experts"], x)


def moe_reference(params, x, n_shards: int, k: int = 1,
                  capacity_factor: float = 1.25, expert_fn=_expert_ffn):
    """Single-device math-identical reference: same per-shard routing and
    capacities as ``moe_apply`` on an ``n_shards``-way mesh."""
    E = params["gate_w"].shape[1]
    T = x.shape[0]
    if T % n_shards:
        raise ValueError(f"tokens {T} not divisible by n_shards={n_shards}")
    C = capacity_for(T // n_shards, E, k, capacity_factor)
    outs, auxes = [], []
    for s in range(n_shards):
        x_local = x[s * (T // n_shards):(s + 1) * (T // n_shards)]
        dispatch, combine, aux = _router(x_local, params["gate_w"], E, k, C)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x_local)
        expert_out = jax.vmap(expert_fn)(params["experts"], expert_in)
        outs.append(jnp.einsum("tec,ecd->td", combine, expert_out))
        auxes.append(aux)
    return jnp.concatenate(outs, axis=0), jnp.mean(jnp.stack(auxes))


def init_moe_params(rng, d_model, d_hidden, num_experts, d_out=None,
                    dtype=np.float32):
    """Initializer for the default FFN experts + router."""
    d_out = d_model if d_out is None else d_out
    s1 = 1.0 / np.sqrt(d_model)
    s2 = 1.0 / np.sqrt(d_hidden)
    return {
        "gate_w": (rng.standard_normal((d_model, num_experts)) * s1
                   ).astype(dtype),
        "experts": {
            "w1": (rng.standard_normal((num_experts, d_model, d_hidden)) * s1
                   ).astype(dtype),
            "b1": np.zeros((num_experts, d_hidden), dtype),
            "w2": (rng.standard_normal((num_experts, d_hidden, d_out)) * s2
                   ).astype(dtype),
            "b2": np.zeros((num_experts, d_out), dtype),
        },
    }


class MoELayer:
    """Stateful convenience wrapper around ``moe_apply`` (trainable)."""

    def __init__(self, d_model, d_hidden, num_experts, mesh, axis="ep",
                 k=1, capacity_factor=1.25, seed=0, batch_axis=None):
        self.mesh, self.axis, self.k = mesh, axis, k
        self.batch_axis = batch_axis
        self.capacity_factor = capacity_factor
        self.params = init_moe_params(np.random.RandomState(seed), d_model,
                                      d_hidden, num_experts)
        self._steps = {}               # (loss_fn id) -> jitted update

    def __call__(self, x):
        y, aux = moe_apply(self.params, x, self.mesh, self.axis, self.k,
                           self.capacity_factor,
                           batch_axis=self.batch_axis)
        self.last_aux_loss = aux
        return y

    def _make_objective(self, loss_fn, x, aux_weight):
        def objective(params):
            y, aux = moe_apply(params, x, self.mesh, self.axis, self.k,
                               self.capacity_factor,
                               batch_axis=self.batch_axis)
            return loss_fn(y) + aux_weight * aux, aux

        return objective

    def grad_step(self, x, loss_fn, lr=0.01, aux_weight=0.01):
        """One SGD step.  ``loss_fn`` must be a stable function object —
        the jitted update is cached per loss_fn (see
        trainer.cached_sgd_step).  Updates ``last_aux_loss``."""
        from .trainer import cached_sgd_step

        step = cached_sgd_step(self._steps, loss_fn,  # mxtpu-lint: donates=0
                               self._make_objective, has_aux=True)
        loss, self.last_aux_loss, self.params = step(self.params, x, lr,
                                                     aux_weight)
        return loss
