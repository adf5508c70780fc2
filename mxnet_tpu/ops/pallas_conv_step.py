"""The decode step of the state-space layers' causal convolution as a
Pallas TPU kernel.

A decode step of a state-space layer convolves each row's input ``u``
(C channels) with the K-1 inputs in front of it, which live in the
row's slot of ONE pool stacked over the layers, and writes the K-1
newest inputs back:

    out = silu(bias + sum_j rows_j * w_j + u * w_{K-1})
    rows <- [rows_1, ..., rows_{K-2}, u]

The pool is ADDRESSED at ``(layer, slots[b])``, never sliced: the
layer and the slots are scalar operands, each live row's slot is one
``make_async_copy`` into VMEM and one back, and the pool is aliased to
the output.  A ``pool[layer]`` slice would copy the layer's whole pool,
and a gather and a scatter around an XLA fusion copy the live rows two
more times and scatter into the whole pool (PERF.md: a layer of a
cache is addressed, never sliced; on one v5e chip the scatter took 29
us a layer where the rows' bytes need 2).  The
layer is an operand and not a constant, and the call is one jitted
function, so a program traces and lowers the kernel once for all its
state layers.

**The pool's layout** (``ops.ssm.conv_rows_shape``): a slot's K-1 rows
side by side, cut into lane rows of 128 and padded to whole sublane
tiles, ``(layers, slots, R, 128)`` with ``R`` = (K-1) C / 128 rounded up
to a multiple of 8 (granite: 102 -> 104), so that the slot axis is a
major one and a slot is whole tiles a DMA can address.  With the rows
flat, ``(layers, slots, (K-1) C)``, a slot is a half-sublane strided
through every tile of the layer, which a DMA cannot address.  Row ``j``
of the K-1 is lane rows ``[j C / 128, (j + 1) C / 128)``, so the kernel
needs C to be a multiple of 128; elsewhere the pool keeps the flat
layout and the XLA form runs (``ops.ssm.conv_state_step``).

The whole bucket is ONE grid step (a grid step a row would cost a
third of a microsecond of bookkeeping each): every live row's copy in
is started at once, then row by row the kernel waits for the row's
rows, convolves them in float32 with the taps in
``ops.ssm.causal_conv``'s order (so a live row's output and new rows
equal the XLA form's bit for bit) and starts the row's copy out; the
copies out are waited for last.  A dead row, one that names the null
slot 0 (a bucket's padding), copies nothing and computes nothing, and
its output is 0; the null slot is never written.  On one v5e chip at
granite's 32-row bucket the kernel takes 3.9 us a layer where the XLA
gather and scatter took 42 us (PERF.md, section 6); convolving the
whole bucket as one array took 5.2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..lint.annotations import hot_path
from . import pallas_util
from .pallas_util import idx32
from .ssm import CONV_LANES, conv_rows_shape

__all__ = ["conv_step_kernel", "KERNEL_NAME"]

# the kernel's name in a device trace; it must not contain the state
# kernel's name, which ``kernel.ssm_update_roofline`` sums by
KERNEL_NAME = "conv_state_step"


def _kernel(slots_ref, layer_ref, u_ref, w_ref, b_ref, pool_ref, y_ref,
            pool_out_ref, buf, sems, *, K, R):
    # pool_ref and pool_out_ref are one buffer (aliased): rows are read
    # from the one and written to the other
    B = u_ref.shape[0]
    layer = layer_ref[0]

    def each_live(fn):
        # fn(b, slot) for every row that names a slot
        def body(b, carry):
            slot = slots_ref[b]

            @pl.when(slot != 0)
            def _():
                fn(b, slot)
            return carry
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(B), body, jnp.int32(0))

    # a row's copies signal its own semaphore: a wait is for that row
    def copy_in(b, slot):
        return pltpu.make_async_copy(pool_ref.at[layer, slot], buf.at[b],
                                     sems.at[b])

    def copy_out(b, slot):
        return pltpu.make_async_copy(buf.at[b],
                                     pool_out_ref.at[layer, slot],
                                     sems.at[b])

    def row(b, slot):
        copy_in(b, slot).wait()
        f32 = jnp.float32
        # tap j < K-1 is row j of the slot's rows, tap K-1 this position
        u = u_ref[b]
        taps = [buf[b, j * R:(j + 1) * R, :].astype(u.dtype)
                for j in range(K - 1)] + [u]
        acc = b_ref[...]
        for j in range(K):
            acc = acc + taps[j].astype(f32) * w_ref[j]
        y_ref[b] = (acc * jax.nn.sigmoid(acc)).astype(y_ref.dtype)
        for j in range(K - 1):
            buf[b, j * R:(j + 1) * R, :] = taps[j + 1].astype(buf.dtype)
        copy_out(b, slot).start()

    each_live(lambda b, slot: copy_in(b, slot).start())
    each_live(row)
    each_live(lambda b, slot: copy_out(b, slot).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pool, layer, slots, u, weight, bias, interpret):
    B, C = u.shape
    K = weight.shape[1]
    R = C // CONV_LANES
    f32 = jnp.float32
    # tap j's weights as the rows lie: (K, R, 128)
    w = jnp.swapaxes(weight.astype(f32), 0, 1).reshape(K, R, CONV_LANES)
    b = bias.astype(f32).reshape(R, CONV_LANES)

    def vmem(shape):
        return pl.BlockSpec(shape, idx32(
            lambda i, sl, ly, n=len(shape): (0,) * n))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[vmem((B, R, CONV_LANES)), vmem((K, R, CONV_LANES)),
                  vmem((R, CONV_LANES)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vmem((B, R, CONV_LANES)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((B,) + pool.shape[2:], pool.dtype),
                        pltpu.SemaphoreType.DMA((B,))],
    )
    y, pool = pl.pallas_call(
        functools.partial(_kernel, K=K, R=R),
        grid_spec=grid_spec,
        # the pool stays in HBM: left to XLA, a pool that fits the
        # chip's VMEM (nemotron's 20 MB) is copied in and out around
        # every call.  The program must donate the pool: where it does
        # not, the chip's compiler aborts on the copy it then adds
        out_shape=[jax.ShapeDtypeStruct((B, R, CONV_LANES), u.dtype),
                   pltpu.HBM(pool.shape, pool.dtype)],
        # operands 0-1 are scalar-prefetched; the pool is operand 5
        input_output_aliases={5: 1},
        name=KERNEL_NAME,
        interpret=interpret,
    )(slots, layer.reshape(1), u.reshape(B, R, CONV_LANES), w, b, pool)
    # a dead row's output block was never written
    y = jnp.where((slots != 0)[:, None], y.reshape(B, C), 0)
    return y.astype(u.dtype), pool


@hot_path
def conv_step_kernel(pool, layer, slots, u, weight, bias, interpret=None):
    """Same contract as ``ops.ssm.conv_state_step``: pool (L, S, R, 128)
    in the lane-row layout (``ops.ssm.conv_rows_shape``); ``layer`` an
    int or an int32 scalar; slots (B,) int32; u (B, C); weight (C, K);
    bias (C,).  Returns ``(silu(conv) (B, C) in u's dtype, the pool)``."""
    C, K = weight.shape
    if pool.ndim != 4 or pool.shape[2:] != conv_rows_shape(K, C):
        raise ValueError(f"conv_state_step: a pool of {pool.shape} does "
                         f"not hold rows of {K - 1} x {C} in lane rows")
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    return _call(pool, jnp.asarray(layer, jnp.int32),
                 jnp.asarray(slots, jnp.int32), u, weight, bias,
                 # mxtpu-lint: disable=host-sync (static host flag chosen
                 # at trace time, never a device value)
                 interpret=bool(interpret))
