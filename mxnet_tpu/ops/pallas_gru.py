"""Fused GRU layer as Pallas TPU kernels.

Companion to ops/pallas_lstm.py (see its module docstring for the
design rationale): the whole time loop runs as one sequential grid with
the 3HxH recurrent weights and hidden state resident in VMEM, instead
of a `lax.scan` that re-streams the weights from HBM every step.  The
reference's fused-RNN coverage (cudnn_rnn-inl.h) includes GRU; this
completes the TPU-era equivalent for the second gated cell.

Gate math matches ops/rnn.py's scan cell exactly (r/z/n order, reset
gate applied to the hidden projection before tanh — the cuDNN/linear-
before-reset variant):

    hp = h @ Wh^T + bh;   r = sig(rx + hp_r);  z = sig(zx + hp_z)
    n  = tanh(nx + r * hp_n);   h' = (1 - z) * n + z * h

Forward saves (r, z, n, hp_n) per step; the reverse-streamed backward
kernel reconstructs every gradient from them with no recomputation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_util
from .pallas_lstm import fused_lstm_eligible
from .pallas_util import idx32

__all__ = ["fused_gru", "fused_gru_eligible"]


def _sig(x):
    return jax.nn.sigmoid(x)


# -- forward ------------------------------------------------------------------

def _fwd_kernel(gx_ref, h0_ref, wh_ref, bh_ref, *refs, T, H, save):
    if save:
        ys_ref, hT_ref, acts_ref, h_sc = refs
    else:
        ys_ref, hT_ref, h_sc = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_sc[:] = h0_ref[:].astype(jnp.float32)

    # recurrent matmul in the ACTIVATION dtype (bf16 MXU fast path),
    # keyed off gx like the flash kernels; carried state stays f32 in
    # scratch, accumulation f32 via preferred_element_type
    dt_lo = gx_ref.dtype
    hp = (jax.lax.dot_general(h_sc[:].astype(dt_lo),
                              wh_ref[:].astype(dt_lo),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + bh_ref[0].astype(jnp.float32))           # (N, 3H)
    gx = gx_ref[0].astype(jnp.float32)
    r = _sig(gx[:, 0 * H:1 * H] + hp[:, 0 * H:1 * H])
    z = _sig(gx[:, 1 * H:2 * H] + hp[:, 1 * H:2 * H])
    nh = hp[:, 2 * H:3 * H]
    n = jnp.tanh(gx[:, 2 * H:3 * H] + r * nh)
    h = (1.0 - z) * n + z * h_sc[:]
    if save:
        acts_ref[0] = jnp.concatenate([r, z, n, nh], axis=-1)
    ys_ref[0] = h.astype(ys_ref.dtype)
    h_sc[:] = h

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h.astype(hT_ref.dtype)


def _fwd(gx, h0, wh, bh, interpret, save):
    """``save=False`` skips the backward residuals (see pallas_lstm)."""
    T, N, G = gx.shape
    H = G // 3
    kernel = functools.partial(_fwd_kernel, T=T, H=H, save=save)
    full = idx32(lambda t: (0, 0))
    step3 = idx32(lambda t: (t, 0, 0))
    out_specs = [pl.BlockSpec((1, N, H), step3),
                 pl.BlockSpec((N, H), full)]
    out_shape = [jax.ShapeDtypeStruct((T, N, H), gx.dtype),   # ys
                 jax.ShapeDtypeStruct((N, H), gx.dtype)]      # hT
    if save:
        out_specs.append(pl.BlockSpec((1, N, 4 * H), step3))
        out_shape.append(
            jax.ShapeDtypeStruct((T, N, 4 * H), jnp.float32))  # r,z,n,nh
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, G), step3),
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, G), full),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, H), jnp.float32)],
        interpret=interpret,
    )(gx, h0, wh, bh)


# -- backward -----------------------------------------------------------------

def _bwd_kernel(acts_ref, hprev_ref, h0_ref, wh_ref, dys_ref, dhT_ref,
                dgx_ref, dwh_ref, dbh_ref, dh0_ref,
                dh_sc, dwh_sc, dbh_sc, *, T, H):
    rt = pl.program_id(0)
    t = T - 1 - rt

    @pl.when(rt == 0)
    def _():
        dh_sc[:] = dhT_ref[:].astype(jnp.float32)
        dwh_sc[:] = jnp.zeros_like(dwh_sc)
        dbh_sc[:] = jnp.zeros_like(dbh_sc)

    acts = acts_ref[0]
    r = acts[:, 0 * H:1 * H]
    z = acts[:, 1 * H:2 * H]
    n = acts[:, 2 * H:3 * H]
    nh = acts[:, 3 * H:4 * H]
    h_prev = jnp.where(t == 0, h0_ref[:].astype(jnp.float32),
                       hprev_ref[0].astype(jnp.float32))

    dh = dh_sc[:] + dys_ref[0].astype(jnp.float32)
    dz = dh * (h_prev - n)
    dn = dh * (1.0 - z)
    dn_pre = dn * (1.0 - n * n)
    dr = dn_pre * nh
    dnh = dn_pre * r
    dr_pre = dr * r * (1.0 - r)
    dz_pre = dz * z * (1.0 - z)
    dgates = jnp.concatenate([dr_pre, dz_pre, dn_pre], axis=-1)  # d gx
    dhp = jnp.concatenate([dr_pre, dz_pre, dnh], axis=-1)        # d hp

    dgx_ref[0] = dgates.astype(dgx_ref.dtype)
    # matmul operands in the activation dtype (MXU fast path, f32 acc)
    dt_lo = dgx_ref.dtype
    dhp_lo = dhp.astype(dt_lo)
    dwh_sc[:] += jax.lax.dot_general(dhp_lo, h_prev.astype(dt_lo),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dbh_sc[0, :] += jnp.sum(dhp, axis=0)
    dh_sc[:] = dh * z + jnp.dot(dhp_lo, wh_ref[:].astype(dt_lo),
                                preferred_element_type=jnp.float32)

    @pl.when(rt == T - 1)
    def _():
        dh0_ref[:] = dh_sc[:].astype(dh0_ref.dtype)
        dwh_ref[:] = dwh_sc[:].astype(dwh_ref.dtype)
        dbh_ref[0] = dbh_sc[0].astype(dbh_ref.dtype)


def _bwd_call(acts, ys, h0, wh, dys, dhT, out_dtype, interpret):
    T, N, _ = acts.shape
    H = ys.shape[-1]
    G = 3 * H
    kernel = functools.partial(_bwd_kernel, T=T, H=H)
    full = idx32(lambda rt: (0, 0))
    rev = idx32(lambda rt: (T - 1 - rt, 0, 0))
    rev_m1 = idx32(lambda rt: (jnp.maximum(T - 2 - rt, 0), 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, 4 * H), rev),    # acts[t]
            pl.BlockSpec((1, N, H), rev_m1),     # ys[t-1] == h_{t-1}
            pl.BlockSpec((N, H), full),
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, N, H), rev),        # dys[t]
            pl.BlockSpec((N, H), full),
        ],
        out_specs=[
            pl.BlockSpec((1, N, G), rev),
            pl.BlockSpec((G, H), full),
            pl.BlockSpec((1, G), full),
            pl.BlockSpec((N, H), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N, G), out_dtype),
            jax.ShapeDtypeStruct((G, H), jnp.float32),
            jax.ShapeDtypeStruct((1, G), jnp.float32),
            jax.ShapeDtypeStruct((N, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((G, H), jnp.float32),
            pltpu.VMEM((1, G), jnp.float32),
        ],
        interpret=interpret,
    )(acts, ys, h0, wh, dys, dhT)


# -- public entry with custom VJP ---------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused(gx, h0, wh, bh, interpret):
    # undifferentiated path (inference): no residual output
    ys, hT = _fwd(gx, h0, wh, bh, interpret, save=False)
    return ys, hT


def _fused_fwd(gx, h0, wh, bh, interpret):
    ys, hT, acts = _fwd(gx, h0, wh, bh, interpret, save=True)
    return (ys, hT), (acts, ys, h0, wh, bh)


def _fused_bwd(interpret, res, grads):
    acts, ys, h0, wh, bh = res
    dys, dhT = grads
    dgx, dwh, dbh, dh0 = _bwd_call(
        acts, ys, h0, wh, dys.astype(ys.dtype), dhT.astype(ys.dtype),
        ys.dtype, interpret)
    return (dgx, dh0.astype(h0.dtype), dwh.astype(wh.dtype),
            dbh.astype(bh.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_gru_eligible(T, N, H, force=None):
    """Same gates as the LSTM kernel (alignment/VMEM rules are
    identical; the GRU weight block is smaller, so the LSTM bound is
    conservative)."""
    return fused_lstm_eligible(T, N, H, force=force)


def fused_gru(gx, h0, wh, bh, interpret=None):
    """One GRU layer over precomputed gate inputs.

    Args:
      gx: (T, N, 3H) input projection incl. input bias (x @ Wi^T + bi).
      h0: (N, H) initial state.
      wh: (3H, H) recurrent weights; bh: (3H,) recurrent bias.
      interpret: run through the Pallas interpreter (default: off-TPU).

    Returns ``(ys, hT)``; differentiable w.r.t. all four arrays.
    """
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    T, N, G = gx.shape
    H = G // 3
    if wh.shape != (G, H):
        raise ValueError(f"wh must be {(G, H)}, got {wh.shape}")
    return _fused(gx, h0.astype(jnp.float32), wh, bh.reshape(1, G),
                  bool(interpret))
