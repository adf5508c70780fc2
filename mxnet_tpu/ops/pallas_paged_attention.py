"""Paged-attention decode as a Pallas TPU kernel.

The serving decode hot loop (``ops.attention.paged_attention``) is a
jnp gather + masked softmax: XLA materializes each request's whole
logical K/V view ``(B, S, Hkv, Dh)`` in HBM before attending, even
though a decode step only *reads* ``context_lens`` tokens of it.  This
kernel is the Mosaic follow-up the jnp docstring names, in the shape of
jax's own TPU paged attention (``pages_per_compute_block``): the grid
walks the batch rows, and INSIDE a row a loop walks the row's live
context a TILE at a time.  A tile is ``T = PAGED_TILE_TOKENS /
block_size`` table slots; the caches stay in HBM (``memory_space=ANY``)
and each of the tile's blocks is one ``make_async_copy`` through the
scalar-prefetched block table into a double-buffered VMEM scratch, the
next tile's copies (at a row's end the next row's first tile's) in
flight while this tile is folded into flash-style running max /
sum-exp / f32 accumulators: ONE score product, one softmax update and
one ``p x V`` product per tile.  Row ``b`` walks tiles ``[lo_b,
cdiv(ctx_b, T * block_size))`` and nothing else (``lo_b`` is 0, or the
first tile of the window band): a table slot past the context costs no
DMA, no branch and no grid step.  No gathered copy of the cache ever
exists; HBM traffic is the live context rounded up to a tile.

The cache operand is the engine's WHOLE stacked pool ``(L, num_blocks,
block_size, Hkv, Dh)`` and the layer to read is a static index in each
copy's source address, ``(layer, bt[b, slot])``.  A custom call needs
each operand as a buffer of its own, so a caller that sliced one layer
out of the stack first (``cache[i]``) made XLA copy that layer's whole
pool, K and V, every layer of every step (PERF.md, PR 27).  Callers
pass the stack and ``layer=i``; a lone 4-D cache is the same kernel
through ``cache[None]``.

Two kernels share that walk (``_walk``) and differ in a tile's
arithmetic.  Heads of 128 (``paged_attention_kernel``): a block is seen
as the matrix ``(block_size * Hkv, Dh)`` it already is in memory, rows
ordered (position, kv head), so a tile is ONE dense ``(T * block_size *
Hkv, Dh)`` operand and all query heads score it at once, ``(Hq, Dh) x
(Dh, T * block_size * Hkv)``; an additive mask keeps each query head's
own kv head.  Reading one head out of the tile instead picks a sublane
of every position's ``(Hkv, Dh)`` tile and was what the kernel's time
went to (0.72 us a 16-token block; PERF.md, PR 30).  A FLAT cache
(``paged_attention_packed_kernel``): the lanes hold ``128 / Dh`` kv
heads side by side and the queries are block-diagonal, one ``(rows,
128) x (128, T * block_size)`` product per lane group (heads of 128: a
lane group is one kv head and its rows that head's query heads).
With int8 KV blocks (``k_scale``/``v_scale`` per-slot-per-head f32
scales) the scales ride the same tile copies and the dequantize happens
in VMEM, so the HBM read is the int8 bytes.

Padded table rows point at the null block (id 0); their slots sit at or
beyond ``context_lens``, and a slot past the row's last live block is
never addressed (a tile's tail re-reads the last live block and the
position mask drops it).  A fully-empty row (``context_lens == 0``)
walks no tile: its accumulator stays zero and the output is zeros,
matching the jnp path's empty-row guard.

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
                        paged_tile_slots, score_scale)
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


# -- the walk both kernels share ----------------------------------------------

def _walk(bt_ref, ctx_ref, streams, sems, slot_ref, fold, carry, *, layer,
          T, bs, window):
    """Fold the live tiles of row ``program_id(0)`` into ``carry``.

    ``streams`` pairs each HBM operand ``(L, num_blocks, rows, ...)`` with
    its VMEM scratch ``(2, T * rows, ...)``; ``sems`` is ``(len(streams),
    2)`` DMA semaphores, one per stream and buffer.  ``fold(buf, base,
    ctx, carry)`` folds the tile in buffer ``buf`` whose first position is
    ``base``.  ``slot_ref`` (SMEM, one int32) carries the buffer of a
    row's first tile from row to row: the grid is sequential and a row's
    last step starts the next row's first tile."""
    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    span = T * bs

    def cdiv(x, n):
        # lax.div on int32 (nothing here is negative): jnp's floor
        # division does not lower inside a Mosaic kernel under x64
        return jax.lax.div(x + jnp.int32(n - 1), jnp.int32(n))

    def tiles(r):
        ctx = ctx_ref[r]
        lo = (jax.lax.div(jnp.maximum(ctx - window, 0), jnp.int32(span))
              if window else jnp.int32(0))
        return lo, cdiv(ctx, span)

    def copies(r, t, buf):
        """Tile ``t`` of row ``r`` into buffer ``buf``: slot by slot,
        never past the row's last live block (what is read twice the
        position mask drops)."""
        live = jnp.maximum(cdiv(ctx_ref[r], bs) - 1, 0)
        out = []
        for i in range(T):
            blk = bt_ref[r, jnp.minimum(t * T + i, live)]
            out += _slot_copies(streams, sems, layer, blk, buf, i, T)
        return out

    lo, hi = tiles(b)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = jnp.int32(0)

    first = slot_ref[0]
    plo, phi = tiles(jnp.maximum(b - 1, 0))
    nxt = jnp.minimum(b + 1, last_row)
    nlo, nhi = tiles(nxt)
    next_live = jnp.logical_and(b < last_row, nhi > nlo)

    # the row before this one started this row's first tile, unless it
    # walked nothing (or there is none)
    @pl.when(jnp.logical_and(hi > lo,
                             jnp.logical_or(b == 0, phi <= plo)))
    def _():
        for c in copies(b, lo, first):
            c.start()

    def step(t, carry):
        buf = jnp.bitwise_and(first + (t - lo), 1)
        more = t + 1 < hi

        @pl.when(jnp.logical_or(more, next_live))
        def _():
            for c in copies(jnp.where(more, b, nxt),
                            jnp.where(more, t + 1, nlo), 1 - buf):
                c.start()

        for i in range(T):
            for c in _slot_copies(streams, sems, layer, 0, buf, i, T):
                c.wait()
        return fold(buf, t * span, ctx_ref[b], carry)

    carry = jax.lax.fori_loop(lo, hi, step, carry)
    slot_ref[0] = jnp.bitwise_and(first + (hi - lo), 1)
    return carry


def _slot_copies(streams, sems, layer, blk, buf, i, T):
    """One block of every stream into slot ``i`` of buffer ``buf``."""
    out = []
    for n, (hbm, vmem) in enumerate(streams):
        rows = vmem.shape[1] // T
        out.append(pltpu.make_async_copy(
            # int32 indices: under x64 a Python int is an i64 Mosaic
            # cannot slice a memref with
            hbm.at[jnp.int32(layer), jnp.int32(blk)],
            vmem.at[buf, pl.ds(i * rows, rows)],
            sems.at[jnp.int32(n), buf]))
    return out


def _lanes(x):
    """``(T, n)`` -> ``(1, T * n)``: the tile's slots side by side."""
    return jnp.concatenate([x[i:i + 1] for i in range(x.shape[0])], axis=1)


def _softmax_fold(s, v, m_prev, l_prev, acc, p_scale=None):
    """One flash-style update: scores ``s`` (rows, columns) with
    ``_NEG_INF`` in every masked column, values ``v`` (columns, width),
    ``p_scale`` (1, columns) what a column's value is still to be
    multiplied by (int8 V).
    Every row of a walked tile keeps a column (the walk visits no tile
    outside ``[lo, hi)``), so the running max is finite and a masked
    column's ``exp`` is exactly 0."""
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if p_scale is not None:
        p = p * p_scale
    # p cast to v's dtype keeps a bf16 cache's PV matmul on the fast MXU
    # pass (dequantized int8 is already f32)
    acc = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_cur, l_cur, acc


def _normalized(acc, l_row, dtype):
    # a fully-masked row (context_lens == 0) accumulated nothing: emit
    # zeros, never 0/0 NaN
    return jnp.where(l_row > _ZERO, acc / jnp.maximum(l_row, _TINY),
                     _ZERO).astype(dtype)


def _call(kernel, block_tables, context_lens, q_like, whole, caches, T, *,
          name, interpret):
    """The ``pallas_call`` both kernels make: a grid over the rows, the
    row's queries in and its outputs out as blocks, the ``whole``
    operands resident in VMEM, the caches left in HBM for the walk's own
    copies into a double buffer of one tile (``T`` slots) each."""
    B = q_like.shape[0]
    row = (1,) + q_like.shape[1:]
    per_row = idx32(lambda b, bt, ctx: (b,) + (0,) * (len(row) - 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec(row, per_row)]
        + [pl.BlockSpec(w.shape, idx32(
            lambda b, bt, ctx, n=w.ndim: (0,) * n)) for w in whole]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(caches),
        out_specs=pl.BlockSpec(row, per_row),
        scratch_shapes=[
            pltpu.VMEM((2, T * c.shape[2]) + c.shape[3:], c.dtype)
            for c in caches] + [
            pltpu.SemaphoreType.DMA((len(caches), 2)),
            pltpu.SMEM((1,), jnp.int32)],
    )
    kw = {}
    if not interpret:
        # sequential: a row starts the next row's first copies and hands
        # it the buffer they land in
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_like.shape, q_like.dtype),
        name=name,
        # mxtpu-lint: disable=host-sync (static host flag chosen at
        # trace time — never a device value, nothing to sync)
        interpret=bool(interpret),
        **kw,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), q_like, *whole, *caches)


# -- heads of 128: every kv head of a position is a row of the tile -----------

def _kernel(bt_ref, ctx_ref, q_ref, mask_ref, *rest, scale, layer, T, bs,
            Hkv, window, quant):
    """Row ``b``: all ``Hq`` query heads against tiles whose rows are
    (position, kv head).  ``mask_ref`` (Hq, T * bs * Hkv) is 0 where the
    column's kv head is the query head's own and ``_NEG_INF`` elsewhere.
    With ``quant`` the K/V streams are int8 and two per-slot-per-head
    scale streams follow them."""
    n = 4 if quant else 2
    hbm, (o_ref, *vmem, sems, slot_ref) = rest[:n], rest[n:]
    q = q_ref[0]                                        # (Hq, Dh)
    Hq, Dh = q.shape
    cols = T * bs * Hkv
    col = jax.lax.broadcasted_iota(jnp.int32, (Hq, cols), 1)

    def fold(buf, base, ctx, carry):
        k, v = vmem[0][buf], vmem[1][buf]               # (cols, Dh)
        if quant:
            # fused dequant in VMEM: the HBM stream was int8.  A slot's
            # scales are a row of its columns' order, so they scale the
            # slot's scores and probabilities, not its K and V rows; the
            # integers themselves are exact in q's dtype
            k, v = k.astype(q.dtype), v.astype(jnp.float32)
            ks, vs = vmem[2][buf], vmem[3][buf]         # (T, cols / T)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quant:
            s = s * _lanes(ks)
        s = s * scale + mask_ref[...]
        # column c holds position base + c // Hkv: no division needed
        keep = col < (ctx - base) * Hkv
        if window:
            keep = jnp.logical_and(
                keep, col >= (ctx - window - base) * Hkv)
        return _softmax_fold(jnp.where(keep, s, _NEG_INF), v, *carry,
                             p_scale=_lanes(vs) if quant else None)

    carry = (jnp.full((Hq, 1), _NEG_INF), jnp.zeros((Hq, 1), jnp.float32),
             jnp.zeros((Hq, Dh), jnp.float32))
    _, l_row, acc = _walk(bt_ref, ctx_ref, list(zip(hbm, vmem)), sems,
                          slot_ref, fold, carry, layer=layer, T=T, bs=bs,
                          window=window)
    o_ref[0] = _normalized(acc, l_row, o_ref.dtype)


@hot_path
def paged_attention_kernel(q, k_cache, v_cache, block_tables,
                           context_lens, window=0, scale=None,
                           k_scale=None, v_scale=None, interpret=None,
                           layer=None):
    """Single-token paged decode attention, a tile of the context a step.

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
    T = paged_tile_slots(bs)
    # a block as the (positions x kv heads, Dh) matrix it is in memory:
    # the same bytes, no copy (tests/test_perf_contract.py reads the
    # compiled decode program for one)
    rows = (L, nb, bs * Hkv)
    caches = [k_cache.reshape(rows + (Dh,)), v_cache.reshape(rows + (Dh,))]
    if quant:
        # a block's scales as one row in its columns' order
        caches += [k_scale.astype(jnp.float32).reshape(L, nb, 1, bs * Hkv),
                   v_scale.astype(jnp.float32).reshape(L, nb, 1, bs * Hkv)]
    own = (np.arange(T * bs * Hkv)[None, :] % Hkv
           == np.arange(Hq)[:, None] // group)
    mask = jnp.asarray(np.where(own, _ZERO, _NEG_INF))

    kernel = functools.partial(_kernel, scale=scale, layer=layer, T=T,
                               bs=bs, Hkv=Hkv, window=int(window),
                               quant=quant)
    return _call(kernel, block_tables, context_lens, q, [mask], caches, T,
                 name="paged_attention", interpret=interpret)


# -- small heads: several kv heads side by side on the lanes ------------------

def _packed_kernel(bt_ref, ctx_ref, q_ref, k_hbm, v_hbm, o_ref, k_vmem,
                   v_vmem, sems, slot_ref, *, scale, layer, T, bs):
    """Row ``b`` over a cache whose minor axis holds every kv head of a
    position side by side.  Lane group ``j`` (128 lanes) holds ``pack``
    kv heads; the query block is block-diagonal (the rows of head ``p``
    are zero outside its own lanes), so ONE lane-aligned product per
    lane group gives a tile's scores for its heads and one more their
    outputs.

    What PR 29 tried on the old slot-a-step walk and took out (8 slots'
    DMAs a step, dead slots' DMAs spared: 10.2 -> 10.6 and 12.0 ms for 4
    layers x 64 rows) left the per-slot branch and the 16-position
    products in place; the time was those (PERF.md, PR 30)."""
    _, Hp, rows, _ = q_ref.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, T * bs), 1)

    def fold(buf, base, ctx, carry):
        keep = pos < ctx - base
        out = []
        for j in range(Hp):
            lanes = slice(j * 128, (j + 1) * 128)
            s = jax.lax.dot_general(
                q_ref[0, j], k_vmem[buf, :, lanes],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            out.append(_softmax_fold(jnp.where(keep, s, _NEG_INF),
                                     v_vmem[buf, :, lanes], *carry[j]))
        return tuple(out)

    carry = ((jnp.full((rows, 1), _NEG_INF),
              jnp.zeros((rows, 1), jnp.float32),
              jnp.zeros((rows, 128), jnp.float32)),) * Hp
    carry = _walk(bt_ref, ctx_ref, [(k_hbm, k_vmem), (v_hbm, v_vmem)], sems,
                  slot_ref, fold, carry, layer=layer, T=T, bs=bs, window=0)
    for j, (_, l_row, acc) in enumerate(carry):
        o_ref[0, j] = _normalized(acc, l_row, o_ref.dtype)


@hot_path
def paged_attention_packed_kernel(q, k_cache, v_cache, block_tables,
                                  context_lens, layer, scale=None,
                                  interpret=None):
    """Paged decode attention over a FLAT stacked cache ``(L, num_blocks,
    block_size, Hkv * Dh)``: heads smaller than the 128 lanes, or few
    heads of 128 (two of them pad a ``(16, 128)`` tile eightfold).

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
    T = paged_tile_slots(bs)
    # block-diagonal queries: head p's rows are zero outside its lanes
    q6 = q.reshape(B, Hp, pack, group, 1, Dh)
    eye = jnp.eye(pack, dtype=q.dtype)[None, None, :, None, :, None]
    q2 = (q6 * eye).reshape(B, Hp, rows, 128)

    caches = [k_cache, v_cache]
    kernel = functools.partial(_packed_kernel, scale=scale, layer=layer,
                               T=T, bs=bs)
    out = _call(kernel, block_tables, context_lens, q2, [], caches, T,
                name="paged_attention_packed", interpret=interpret)
    o6 = out.reshape(B, Hp, pack, group, pack, Dh)
    own = jnp.stack([o6[:, :, p, :, p, :] for p in range(pack)], axis=2)
    return own.reshape(B, Hq, Dh)
