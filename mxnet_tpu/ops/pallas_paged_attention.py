"""Paged-attention decode as a Pallas TPU kernel.

The serving decode hot loop (``ops.attention.paged_attention``) is a
jnp gather + masked softmax: XLA materializes each request's whole
logical K/V view ``(B, S, Hkv, Dh)`` in HBM before attending, even
though a decode step only *reads* ``context_lens`` tokens of it.  This
kernel is the Mosaic follow-up the jnp docstring names: the grid walks
``(batch, table_slot)`` and streams ONE physical K/V block per step
from HBM into VMEM through the request's block table (scalar-prefetched
so the DMA's source index is known before the body runs — the
vLLM-PagedAttention formulation on TPU), updating flash-style running
max / sum-exp / f32 accumulators per kv head.  No gathered copy of the
cache ever exists; HBM traffic is exactly the live context bytes.

The cache operand is the engine's WHOLE stacked pool ``(L, num_blocks,
block_size, Hkv, Dh)`` and the layer to read is a static index in the
DMA's source address, ``(layer, bt[b, w], 0, 0, 0)``.  A custom call
needs each operand as a buffer of its own, so a caller that sliced one
layer out of the stack first (``cache[i]``) made XLA copy that layer's
whole pool, K and V, every layer of every step — half of a decode
step's device time (PERF.md, PR 27).  Callers pass the stack and
``layer=i``; a lone 4-D cache is the same kernel through ``cache[None]``.

Grouped-query attention is native: the kernel loops the (static) kv
heads and each grid step's block fetch serves every q head of the
group — with int8 KV blocks (``k_scale``/``v_scale`` per-slot-per-head
f32 scales) the dequantize happens in VMEM, fused into the same pass,
so the HBM read is the int8 bytes.

Padded table rows point at the null block (id 0); their positions sit
at or beyond ``context_lens`` so the mask (and the compute-skip guard)
drops them, and a fully-empty row (``context_lens == 0``) never runs a
tile — its accumulator stays zero and the output is zeros, matching
the jnp path's empty-row guard.

``interpret=True`` (automatic off-TPU) runs the kernel through the
Pallas interpreter so the parity tests exercise the identical code
path on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..lint.annotations import hot_path
# the single eligibility definition lives with the dispatcher (which
# must be importable without Pallas); re-exported here for the tests
from . import pallas_util
from .attention import (packed_eligible, paged_eligible,  # noqa: F401
                        score_scale)
from .flash_attention import gqa_group
from .pallas_util import idx32

__all__ = ["paged_attention_kernel", "paged_attention_packed_kernel",
           "packed_eligible", "paged_eligible"]

# np.float32, not Python floats: under jax_enable_x64 a bare literal in
# a Mosaic kernel body is a weak f64 constant with no f64->f32 cast
# (same rule as ops/flash_attention.py)
_NEG_INF = np.float32(-1e30)
_ZERO = np.float32(0.0)
_TINY = np.float32(1e-30)


def _kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, *rest, scale, bs, nW,
            Hkv, group, window, quant):
    """One grid step (b, w): stream physical block ``bt[b, w]`` and
    fold its ``bs`` positions into the running softmax state of every
    kv head.  With ``quant`` the K/V refs are int8 and two
    per-slot-per-head scale refs follow them in the input list."""
    if quant:
        ksc_ref, vsc_ref, o_ref, acc, m_sc, l_sc = rest
    else:
        (o_ref, acc, m_sc, l_sc), ksc_ref, vsc_ref = rest, None, None
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    ctx = ctx_ref[b]
    base = w * bs
    # compute-skip: blocks entirely beyond the context (padded table
    # rows -> the null block) or entirely below the window band
    # contribute nothing; the DMA still ran, the math doesn't
    live = base < ctx
    if window:
        live = jnp.logical_and(live, base + bs > ctx - 1 - window)

    @pl.when(live)
    def _():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        keep = pos < ctx
        if window:
            keep = jnp.logical_and(keep, pos > ctx - 1 - window)
        for h in range(Hkv):
            k = k_ref[0, 0, :, h, :]
            v = v_ref[0, 0, :, h, :]
            if quant:
                # fused dequant in VMEM: the HBM stream was int8
                k = k.astype(jnp.float32) * ksc_ref[0, 0, :, h][:, None]
                v = v.astype(jnp.float32) * vsc_ref[0, 0, :, h][:, None]
            q = q_ref[0, h]                              # (group, Dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_sc[h, :, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(keep, jnp.exp(s - m_cur[:, None]), _ZERO)
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_sc[h, :, 0] * alpha + jnp.sum(p, axis=-1)
            # p cast to v's dtype keeps a bf16 cache's PV matmul on the
            # fast MXU pass (dequantized int8 is already f32)
            acc[h] = acc[h] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h, :, 0] = m_cur
            l_sc[h, :, 0] = l_cur

    @pl.when(w == nW - 1)
    def _():
        for h in range(Hkv):
            l_row = l_sc[h, :, 0]
            # a fully-masked row (context_lens == 0) accumulated
            # nothing: emit zeros, never 0/0 NaN
            valid = l_row > _ZERO
            l_fin = jnp.maximum(l_row, _TINY)
            o_ref[0, h] = jnp.where(valid[:, None],
                                    acc[h] / l_fin[:, None],
                                    _ZERO).astype(o_ref.dtype)


def _params(interpret):
    """Batch rows are independent (parallel); the table-slot axis
    carries the running-softmax scratch and must stay sequential."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


@hot_path
def paged_attention_kernel(q, k_cache, v_cache, block_tables,
                           context_lens, window=0, scale=None,
                           k_scale=None, v_scale=None, interpret=None,
                           layer=None):
    """Single-token paged decode attention, block-streamed.

    Same contract as ``ops.attention.paged_attention``: q ``(B, Hq,
    Dh)``, caches ``(L, num_blocks, block_size, Hkv, Dh)`` stacked over
    the layers (int8 when ``k_scale``/``v_scale`` — ``(L, num_blocks,
    block_size, Hkv)`` f32 — are given) with the static ``layer`` to
    read, ``block_tables (B, W)`` int32 padded with the null block,
    ``context_lens (B,)``.  A single layer's 4-D cache (3-D scales,
    ``layer=None``) is the same kernel seen through ``cache[None]``.
    Returns ``(B, Hq, Dh)`` in q's dtype.  Empty rows
    (``context_lens == 0``) return zeros.
    """
    B, Hq, Dh = q.shape
    if (layer is None) != (k_cache.ndim == 4):
        raise ValueError("paged_attention: stacked (L, num_blocks, "
                         "block_size, Hkv, Dh) caches take `layer`, "
                         "a single layer's 4-D caches do not")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged_attention: k_scale and v_scale must be "
                         "given together (quantized K/V blocks carry "
                         "both)")
    quant = k_scale is not None
    if layer is None:
        # a reshape, not a copy: the one kernel below addresses layer 0
        # of a one-layer stack
        layer = 0
        k_cache, v_cache = k_cache[None], v_cache[None]
        if quant:
            k_scale, v_scale = k_scale[None], v_scale[None]
    L, nb, bs, Hkv, _ = k_cache.shape
    layer = int(layer)
    if not 0 <= layer < L:
        raise ValueError(f"paged_attention: layer {layer} outside the "
                         f"cache's {L} layers")
    if window < 0:
        raise ValueError(f"paged_attention: window must be >= 0 "
                         f"(got {window})")
    group = gqa_group(Hq, Hkv)
    scale = score_scale(Dh) if scale is None else np.float32(scale)
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    W = block_tables.shape[1]
    q4 = q.reshape(B, Hkv, group, Dh)

    def blk(*shape):
        """Whole-trailing-dims block (Mosaic: the last two block dims
        must divide the tile or equal the array dims — spanning the
        full (Hkv, Dh) / (Hkv,) trailing axes always satisfies it)."""
        return shape

    # the layer is one more index of the block's DMA source address
    per_req = idx32(lambda b, w, bt, ctx: (b, 0, 0, 0))
    per_blk = idx32(lambda b, w, bt, ctx: (layer, bt[b, w], 0, 0, 0))
    per_blk_sc = idx32(lambda b, w, bt, ctx: (layer, bt[b, w], 0, 0))
    in_specs = [
        pl.BlockSpec(blk(1, Hkv, group, Dh), per_req),
        pl.BlockSpec(blk(1, 1, bs, Hkv, Dh), per_blk),
        pl.BlockSpec(blk(1, 1, bs, Hkv, Dh), per_blk),
    ]
    args = [q4, k_cache, v_cache]
    if quant:
        in_specs += [
            pl.BlockSpec(blk(1, 1, bs, Hkv), per_blk_sc),
            pl.BlockSpec(blk(1, 1, bs, Hkv), per_blk_sc),
        ]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(blk(1, Hkv, group, Dh), per_req),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, Dh), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, nW=W, Hkv=Hkv,
                          group=group, window=int(window), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dh), q.dtype),
        # mxtpu-lint: disable=host-sync (static host flag chosen at
        # trace time — never a device value, nothing to sync)
        interpret=bool(interpret),
        **_params(interpret),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), *args)
    return out.reshape(B, Hq, Dh)


# -- small heads: several kv heads side by side on the lanes ------------------

def _packed_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc,
                   l_sc, *, scale, bs, nW, Hp, rows):
    """One grid step (b, w) over a cache whose minor axis holds every kv
    head of a position side by side.  Lane group ``j`` (128 lanes) holds
    ``pack`` kv heads; the query block is block-diagonal (the rows of
    head ``p`` are zero outside its own lanes), so ONE lane-aligned
    product gives every head's scores and one more its outputs.

    Tried on the chip and taken out again (PERF.md, PR 29): streaming 8
    table slots a step, and naming a dead slot's last live block so that
    its DMA is skipped; neither shortened the kernel (10.2 -> 10.6 and
    12.0 ms for 4 layers x 64 rows), whose time is the per-slot branch
    and the (rows x 128) x (128 x 16) products, not the DMAs."""
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    ctx = ctx_ref[b]
    base = w * bs

    @pl.when(base < ctx)
    def _():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        keep = pos < ctx
        for j in range(Hp):
            k = k_ref[0, 0, :, j * 128:(j + 1) * 128]        # (bs, 128)
            v = v_ref[0, 0, :, j * 128:(j + 1) * 128]
            q = q_ref[0, j]                                  # (rows, 128)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_sc[j, :, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.where(keep, jnp.exp(s - m_cur[:, None]), _ZERO)
            alpha = jnp.exp(m_prev - m_cur)
            l_sc[j, :, 0] = l_sc[j, :, 0] * alpha + jnp.sum(p, axis=-1)
            acc[j] = acc[j] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[j, :, 0] = m_cur

    @pl.when(w == nW - 1)
    def _():
        for j in range(Hp):
            l_row = l_sc[j, :, 0]
            valid = l_row > _ZERO
            l_fin = jnp.maximum(l_row, _TINY)
            o_ref[0, j] = jnp.where(valid[:, None], acc[j] / l_fin[:, None],
                                    _ZERO).astype(o_ref.dtype)


@hot_path
def paged_attention_packed_kernel(q, k_cache, v_cache, block_tables,
                                  context_lens, layer, scale=None,
                                  interpret=None):
    """Paged decode attention over a FLAT stacked cache ``(L, num_blocks,
    block_size, Hkv * Dh)`` for heads smaller than the 128 lanes.

    A cache whose two minor axes are ``(Hkv, Dh) = (8, 64)`` is tiled
    (16, 128) in bfloat16 on the chip: padded fourfold, in HBM and in
    every block the kernel streams.  Flat, a position's heads lie side by
    side and nothing is padded.  Same contract otherwise as
    :func:`paged_attention_kernel` (no window, no int8 scales): q ``(B,
    Hq, Dh)``, returns ``(B, Hq, Dh)``; empty rows return zeros.
    """
    B, Hq, Dh = q.shape
    L, nb, bs, flat = k_cache.shape
    Hkv = flat // Dh
    if flat != Hkv * Dh or not packed_eligible(Hkv, Dh):
        raise ValueError(f"paged_attention: a flat cache of width {flat} "
                         f"does not hold whole lane groups of heads of "
                         f"size {Dh}")
    layer = int(layer)
    if not 0 <= layer < L:
        raise ValueError(f"paged_attention: layer {layer} outside the "
                         f"cache's {L} layers")
    group = gqa_group(Hq, Hkv)
    pack = 128 // Dh
    Hp, rows = Hkv // pack, pack * group
    scale = score_scale(Dh) if scale is None else np.float32(scale)
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    W = block_tables.shape[1]
    # block-diagonal queries: head p's rows are zero outside its lanes
    q6 = q.reshape(B, Hp, pack, group, 1, Dh)
    eye = jnp.eye(pack, dtype=q.dtype)[None, None, :, None, :, None]
    q2 = (q6 * eye).reshape(B, Hp, rows, 128)

    per_req = idx32(lambda b, w, bt, ctx: (b, 0, 0, 0))

    per_blk = idx32(lambda b, w, bt, ctx: (layer, bt[b, w], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[pl.BlockSpec((1, Hp, rows, 128), per_req),
                  pl.BlockSpec((1, 1, bs, flat), per_blk),
                  pl.BlockSpec((1, 1, bs, flat), per_blk)],
        out_specs=pl.BlockSpec((1, Hp, rows, 128), per_req),
        scratch_shapes=[pltpu.VMEM((Hp, rows, 128), jnp.float32),
                        pltpu.VMEM((Hp, rows, 1), jnp.float32),
                        pltpu.VMEM((Hp, rows, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_packed_kernel, scale=scale, bs=bs, nW=W, Hp=Hp,
                          rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, rows, 128), q.dtype),
        name="paged_attention_packed",
        # mxtpu-lint: disable=host-sync (static host flag chosen at
        # trace time, never a device value)
        interpret=bool(interpret),
        **_params(interpret),
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), q2, k_cache, v_cache)
    o6 = out.reshape(B, Hp, pack, group, pack, Dh)
    own = jnp.stack([o6[:, :, p, :, p, :] for p in range(pack)], axis=2)
    return own.reshape(B, Hq, Dh)
