"""Fused LSTM layer as Pallas TPU kernels.

The reference's fused-RNN performance story is the cuDNN v5 kernel
(cudnn_rnn-inl.h); the XLA translation (ops/rnn.py) batches the input
projection into one big MXU gemm and scans the recurrence — but under
a `lax.scan` the recurrent weight matrix streams from HBM on EVERY
step, so the serial part of the layer is HBM-bound: T steps re-read
4H*H weights each (e.g. S=128, H=512 -> ~1 GB of weight traffic for
8 MB of weights).

These kernels run the whole time loop as ONE grid with the recurrent
weights and the (h, c) state resident in VMEM: per step only the
precomputed gate inputs gx[t] stream in and h[t] streams out — weight
traffic drops from O(T * H^2) to O(H^2).  The forward kernel also
writes the post-activation gates and cell states, which the backward
kernel (same structure, reverse-streamed via its index maps) consumes
to produce d_gx, d_Wh, d_bh, d_h0, d_c0 without any recomputation.

Sequential-grid semantics (TPU Pallas executes the grid in order,
scratch persists across steps) are what make the carried state legal —
the same property the flash-attention kernels rely on for their
running-softmax accumulators.

``interpret=True`` (tests, CPU) runs identical kernel code through the
Pallas interpreter.  Eligibility for the jit path is checked by
:func:`fused_lstm_eligible`; `ops/rnn.py` falls back to the scan
otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_util
from .pallas_util import idx32

__all__ = ["fused_lstm", "fused_lstm_eligible"]


def _sigmoid(x):
    return jax.nn.sigmoid(x)


# -- forward ------------------------------------------------------------------

def _fwd_kernel(gx_ref, h0_ref, c0_ref, wh_ref, bh_ref,
                *refs, T, H, save):
    if save:
        ys_ref, hT_ref, cT_ref, acts_ref, cells_ref, h_sc, c_sc = refs
    else:
        ys_ref, hT_ref, cT_ref, h_sc, c_sc = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_sc[:] = h0_ref[:].astype(jnp.float32)
        c_sc[:] = c0_ref[:].astype(jnp.float32)

    # recurrent matmul in the ACTIVATION dtype (bf16 MXU fast path; f32
    # runs the ~4x slower pass) — keyed off gx like the flash kernels,
    # so f32 master weights with bf16 activations still engage it.  The
    # carried state itself stays f32 in scratch for stability across T
    # steps; only matmul operands are cast, accumulation is f32 via
    # preferred_element_type.
    dt_lo = gx_ref.dtype
    gates = (gx_ref[0].astype(jnp.float32)
             + jax.lax.dot_general(h_sc[:].astype(dt_lo),
                                   wh_ref[:].astype(dt_lo),
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             + bh_ref[0].astype(jnp.float32))
    i = _sigmoid(gates[:, 0 * H:1 * H])
    f = _sigmoid(gates[:, 1 * H:2 * H])
    g = jnp.tanh(gates[:, 2 * H:3 * H])
    o = _sigmoid(gates[:, 3 * H:4 * H])
    c = f * c_sc[:] + i * g
    h = o * jnp.tanh(c)
    if save:
        acts_ref[0] = jnp.concatenate([i, f, g, o], axis=-1)
        cells_ref[0] = c
    ys_ref[0] = h.astype(ys_ref.dtype)
    h_sc[:] = h
    c_sc[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h.astype(hT_ref.dtype)
        cT_ref[:] = c.astype(cT_ref.dtype)


def _fwd(gx, h0, c0, wh, bh, interpret, save):
    """``save=False`` (inference / undifferentiated primal) skips the
    residual outputs — a pallas_call cannot have unused outputs DCE'd,
    and the backward residuals are 5x the useful HBM write traffic."""
    T, N, G = gx.shape
    H = G // 4
    kernel = functools.partial(_fwd_kernel, T=T, H=H, save=save)
    full = idx32(lambda t: (0, 0))
    step3 = idx32(lambda t: (t, 0, 0))
    out_specs = [
        pl.BlockSpec((1, N, H), step3),
        pl.BlockSpec((N, H), full),
        pl.BlockSpec((N, H), full),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, N, H), gx.dtype),       # ys
        jax.ShapeDtypeStruct((N, H), gx.dtype),          # hT
        jax.ShapeDtypeStruct((N, H), gx.dtype),          # cT
    ]
    if save:
        out_specs += [pl.BlockSpec((1, N, G), step3),
                      pl.BlockSpec((1, N, H), step3)]
        out_shape += [jax.ShapeDtypeStruct((T, N, G), jnp.float32),
                      jax.ShapeDtypeStruct((T, N, H), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, G), step3),
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, G), full),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((N, H), jnp.float32),
        ],
        interpret=interpret,
    )(gx, h0, c0, wh, bh)


# -- backward -----------------------------------------------------------------

def _bwd_kernel(acts_ref, cells_ref, cprev_ref, hprev_ref, h0_ref, c0_ref,
                wh_ref, dys_ref, dhT_ref, dcT_ref,
                dgx_ref, dwh_ref, dbh_ref, dh0_ref, dc0_ref,
                dh_sc, dc_sc, dwh_sc, dbh_sc, *, T, H):
    rt = pl.program_id(0)          # reverse step; actual time t = T-1-rt
    t = T - 1 - rt

    @pl.when(rt == 0)
    def _():
        dh_sc[:] = dhT_ref[:].astype(jnp.float32)
        dc_sc[:] = dcT_ref[:].astype(jnp.float32)
        dwh_sc[:] = jnp.zeros_like(dwh_sc)
        dbh_sc[:] = jnp.zeros_like(dbh_sc)

    acts = acts_ref[0]
    i = acts[:, 0 * H:1 * H]
    f = acts[:, 1 * H:2 * H]
    g = acts[:, 2 * H:3 * H]
    o = acts[:, 3 * H:4 * H]
    c = cells_ref[0]
    is_first = t == 0
    c_prev = jnp.where(is_first, c0_ref[:].astype(jnp.float32),
                       cprev_ref[0])
    h_prev = jnp.where(is_first, h0_ref[:].astype(jnp.float32),
                       hprev_ref[0].astype(jnp.float32))

    dh = dh_sc[:] + dys_ref[0].astype(jnp.float32)
    tc = jnp.tanh(c)
    do = dh * tc
    dc = dc_sc[:] + dh * o * (1.0 - tc * tc)
    di = dc * g
    df = dc * c_prev
    dg = dc * i
    dgates = jnp.concatenate(
        [di * i * (1.0 - i), df * f * (1.0 - f),
         dg * (1.0 - g * g), do * o * (1.0 - o)], axis=-1)   # (N, 4H)

    dgx_ref[0] = dgates.astype(dgx_ref.dtype)
    # matmul operands in the activation dtype (MXU fast path, f32 acc)
    dt_lo = dgx_ref.dtype
    dg_lo = dgates.astype(dt_lo)
    # dWh += dgates^T @ h_prev : contract over batch
    dwh_sc[:] += jax.lax.dot_general(dg_lo, h_prev.astype(dt_lo),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dbh_sc[0, :] += jnp.sum(dgates, axis=0)
    dh_sc[:] = jnp.dot(dg_lo, wh_ref[:].astype(dt_lo),
                       preferred_element_type=jnp.float32)
    dc_sc[:] = dc * f

    @pl.when(rt == T - 1)
    def _():
        dh0_ref[:] = dh_sc[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_sc[:].astype(dc0_ref.dtype)
        dwh_ref[:] = dwh_sc[:].astype(dwh_ref.dtype)
        dbh_ref[0] = dbh_sc[0].astype(dbh_ref.dtype)


def _bwd_call(acts, cells, ys, h0, c0, wh, dys, dhT, dcT, gx_dtype,
              interpret):
    T, N, G = acts.shape
    H = G // 4
    kernel = functools.partial(_bwd_kernel, T=T, H=H)
    full = idx32(lambda rt: (0, 0))
    rev = idx32(lambda rt: (T - 1 - rt, 0, 0))
    # previous-step streams: block t-1 (clamped at 0; the t==0 value is
    # replaced by h0/c0 inside the kernel)
    rev_m1 = idx32(lambda rt: (jnp.maximum(T - 2 - rt, 0), 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, G), rev),        # acts[t]
            pl.BlockSpec((1, N, H), rev),        # cells[t]
            pl.BlockSpec((1, N, H), rev_m1),     # cells[t-1]
            pl.BlockSpec((1, N, H), rev_m1),     # ys[t-1] == h_{t-1}
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, N, H), rev),        # dys[t]
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((N, H), full),
        ],
        out_specs=[
            pl.BlockSpec((1, N, G), rev),        # dgx[t]
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, G), full),
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((N, H), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N, G), gx_dtype),
            jax.ShapeDtypeStruct((G, H), jnp.float32),
            jax.ShapeDtypeStruct((1, G), jnp.float32),
            jax.ShapeDtypeStruct((N, H), jnp.float32),
            jax.ShapeDtypeStruct((N, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((G, H), jnp.float32),
            pltpu.VMEM((1, G), jnp.float32),
        ],
        interpret=interpret,
    )(acts, cells, cells, ys, h0, c0, wh, dys, dhT, dcT)


# -- public entry with custom VJP ---------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(gx, h0, c0, wh, bh, interpret):
    # undifferentiated path (inference): no residual outputs
    ys, hT, cT = _fwd(gx, h0, c0, wh, bh, interpret, save=False)
    return ys, hT, cT


def _fused_fwd(gx, h0, c0, wh, bh, interpret):
    ys, hT, cT, acts, cells = _fwd(gx, h0, c0, wh, bh, interpret,
                                   save=True)
    return (ys, hT, cT), (acts, cells, ys, h0, c0, wh, bh)


def _fused_bwd(interpret, res, grads):
    acts, cells, ys, h0, c0, wh, bh = res
    dys, dhT, dcT = grads
    dgx, dwh, dbh, dh0, dc0 = _bwd_call(
        acts, cells, ys, h0, c0, wh,
        dys.astype(ys.dtype), dhT.astype(ys.dtype), dcT.astype(ys.dtype),
        ys.dtype, interpret)
    # dbh keeps the (1, G) shape and dtype of the reshaped primal; the
    # outer reshape's own vjp restores (G,)
    return (dgx, dh0.astype(h0.dtype), dc0.astype(c0.dtype),
            dwh.astype(wh.dtype), dbh.astype(bh.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_lstm_eligible(T, N, H, force=None):
    """Whether the fused kernel should carry this layer on the current
    backend.  Lane/sublane alignment keeps Mosaic happy; the VMEM
    budget bounds the weight + dWh accumulator residency.

    ``force`` / ``MXNET_TPU_FUSED_RNN=1`` override the backend and
    sequence-length gates (interpret-mode tests, benchmarking) but the
    Mosaic alignment and VMEM constraints still apply on a real TPU —
    forcing a shape the compiler cannot tile must fall back, not crash.
    """
    import os

    env = os.environ.get("MXNET_TPU_FUSED_RNN", "")
    if env == "0":
        return False
    forced = bool(force) or env == "1"
    on_tpu = pallas_util.on_tpu()
    if on_tpu:
        if H % 128 or N % 8:
            return False
        # VMEM residency: wh + dwh-accumulator f32 (weight term) plus
        # the batch-proportional working set — h/c scratch, the per-step
        # (N,4H)/(N,H) in/out blocks and their pipelining double
        # buffers (~24 (N,H)-equivalents is a conservative count).
        # Oversize shapes must fall back to the scan, not crash Mosaic.
        weight_bytes = 2 * 4 * H * H * 4
        batch_bytes = 24 * N * H * 4
        if weight_bytes + batch_bytes > 12 * 1024 * 1024:
            return False
    if forced:
        return True
    if not on_tpu:
        return False
    return T >= 8  # tiny sequences gain nothing over the scan


def fused_lstm(gx, h0, c0, wh, bh, interpret=None):
    """One LSTM layer over precomputed gate inputs.

    Args:
      gx: (T, N, 4H) input projection incl. input bias (x @ Wi^T + bi).
      h0, c0: (N, H) initial states.
      wh: (4H, H) recurrent weights; bh: (4H,) recurrent bias.
      interpret: run through the Pallas interpreter (default: off-TPU).

    Returns ``(ys, hT, cT)`` with ys (T, N, H).  Differentiable w.r.t.
    all five array arguments (custom VJP, reverse-streamed kernel).
    Gate order i, f, g, o matches ops/rnn.py's scan cell.
    """
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    T, N, G = gx.shape
    H = G // 4
    if wh.shape != (G, H):
        raise ValueError(f"wh must be {(G, H)}, got {wh.shape}")
    return _fused(gx, h0.astype(jnp.float32), c0.astype(jnp.float32),
                  wh, bh.reshape(1, G), bool(interpret))
