"""Span attention of the serve programs as a Pallas TPU kernel.

A span is ``T`` query rows of ONE request at consecutive positions
``start + t`` over ``S`` key positions ``0 .. S-1``: a whole prompt over
its own rows, or a chunk over the block table's gathered view.  The dense
form (``ops.attention.masked_attention``'s other branch) writes the
``(Hq, T, S)`` f32 scores to HBM and reads them back for the mask, the
softmax and ``p x V``: 1.07 GB a layer at 32 heads x 2048 x 4096, most of
it above the diagonal or past the prompt's end.  Here the scores of a
``(block_q, block_k)`` tile live in VMEM only, folded into flash-style
running max / sum-exp / f32 accumulators, and a query tile walks ONLY
the key tiles it can see:

* nothing above its diagonal (``key <= start + row``),
* nothing below its window's band (``key > start + row - window``),
* nothing past the pass's last real row (rows ``>= n_valid`` are padding
  whose outputs are discarded: a tile of them walks nothing and writes
  zeros).

Of the visited tiles only those the diagonal (or the band's edge) cuts
build a mask.  The walk is a ``fori_loop`` with traced bounds inside one
grid step, so a dead tile costs no grid step, no DMA and no branch.

No head is ever picked out of a ``(positions, heads, Dh)`` tile (a
relayout per head and operand: what ``FLASH_BENCH.json``'s kernel and the
paged kernel before PR 30 spent their time on).  The operands are seen as
the 2-D matrices they already are in memory, ``q (T, Hq * Dh)`` and
``k``/``v (S, Hkv * Dh)``: a head is a column block of 128-lane tiles, so a
BlockSpec addresses it and the DMA engine reads whole tiles; no transpose
and no ``repeat`` (query head ``h`` reads kv head ``h // group``; the
grid walks the heads outermost, so a kv head's K/V is fetched once for
its group).  That wants ``Dh`` a multiple of 128; heads of 64 are
zero-padded to the lanes in front of the call (at 512 rows over 4096 keys
0.18 ms against the dense form's 2.0; PERF.md, PR 32).

K/V of a kv head stay resident in VMEM, ``SPAN_RESIDENT_TOKENS``
positions at a time (the whole 4096-position view of the benchmark's
engine); a longer view is walked in that many positions a grid step with
the accumulators carried in scratch, a step's block index clamped onto
the query tile's live range so that a dead step fetches nothing new.

``interpret=True`` (automatic off-TPU) runs the kernel through the
Pallas interpreter so the parity tests exercise the identical code path
on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..lint.annotations import hot_path
from . import pallas_util
from .attention import (SPAN_BLOCK_K, SPAN_BLOCK_Q, SPAN_RESIDENT_TOKENS,
                        span_kernel_eligible)
from .flash_attention import gqa_group
from .pallas_paged_attention import _NEG_INF, _normalized
from .pallas_util import idx32

__all__ = ["span_attention_kernel"]

def _i32(x):
    return jnp.int32(x)


def _div(x, n):
    # lax.div on int32 (nothing here is negative): jnp's floor division
    # does not lower inside a Mosaic kernel under x64
    return jax.lax.div(x, _i32(n))


def _live_keys(span_ref, qi, *, T, S, bq, window):
    """Query tile ``qi``'s first position and the key range ``[lo, hi)``
    its real rows can see (empty when the tile is all padding)."""
    start, end = span_ref[0], span_ref[0] + span_ref[1]
    p0 = start + jax.lax.rem(qi, _i32(T // bq)) * _i32(bq)
    hi = jnp.minimum(jnp.minimum(p0 + _i32(bq), end), _i32(S))
    lo = jnp.maximum(p0 - _i32(window - 1), 0) if window else _i32(0)
    return p0, lo, jnp.where(p0 < end, hi, lo)


def _fold(s, v, m_prev, l_prev, acc):
    """``_softmax_fold`` of the paged kernels with the running sum kept a
    LANE at a time, ``l (rows, 128)``: a tile's probabilities are added
    column block onto column block (vector adds) and the lanes are summed
    once, when the walk is over.  The row max still crosses the lanes
    every tile; the sum's second crossing was 7 % of the kernel at (2048,
    4096) (PERF.md, PR 32)."""
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    lanes = p[:, :128]
    for j in range(1, p.shape[1] // 128):
        lanes = lanes + p[:, j * 128:(j + 1) * 128]
    acc = acc * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_cur, l_prev * alpha + lanes, acc


def _kernel(span_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, window, T, S, bq, bk, resident):
    """Query tile ``program_id(1)`` of head ``program_id(0)`` over the
    ``resident`` key positions of grid step ``program_id(2)``."""
    qi, kb = pl.program_id(1), pl.program_id(2)
    nkb = S // resident
    p0, lo, hi = _live_keys(span_ref, qi, T=T, S=S, bq=bq, window=window)
    # key tiles of this step's resident positions, as global tile indices
    base = kb * _i32(resident // bk)
    t_lo = jnp.maximum(_div(lo, bk), base)
    t_hi = jnp.minimum(_div(hi + _i32(bk - 1), bk),
                       base + _i32(resident // bk))
    # tiles every row of the query tile sees whole need no mask: those
    # that end at or before the first row's position (none under a
    # window, whose band's lower edge cuts the early ones)
    t_full = t_lo if window else jnp.clip(_div(p0 + _i32(1), bk),
                                          t_lo, t_hi)

    q = q_ref[...]                                      # (bq, Dh)
    row = p0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def fold(masked):
        def step(t, carry):
            at = pl.ds(pl.multiple_of((t - base) * _i32(bk), bk), bk)
            s = jax.lax.dot_general(
                q, k_ref[at, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                key = t * _i32(bk) + col
                keep = key <= row
                if window:
                    keep = jnp.logical_and(keep, key > row - _i32(window))
                s = jnp.where(keep, s, _NEG_INF)
            return _fold(s, v_ref[at, :], *carry)
        return step

    refs = (m_ref, l_ref, acc_ref)
    carry = (jnp.full(m_ref.shape, _NEG_INF),
             jnp.zeros(l_ref.shape, jnp.float32),
             jnp.zeros(acc_ref.shape, jnp.float32))
    if nkb > 1:
        # the accumulators ride the scratch from one grid step of a query
        # tile to the next (one step: they never leave the loop's carry)
        @pl.when(kb == 0)
        def _():
            for ref, zero in zip(refs, carry):
                ref[...] = zero

        carry = tuple(ref[...] for ref in refs)
    carry = jax.lax.fori_loop(t_lo, t_full, fold(False), carry)
    carry = jax.lax.fori_loop(t_full, jnp.maximum(t_hi, t_full), fold(True),
                              carry)
    if nkb > 1:
        for ref, val in zip(refs, carry):
            ref[...] = val
    _, l_lanes, acc = carry

    @pl.when(kb == nkb - 1)
    def _():
        # a tile of padding walked nothing: zeros, never 0/0
        o_ref[...] = _normalized(
            acc, jnp.sum(l_lanes, axis=-1, keepdims=True), o_ref.dtype)


@hot_path
def span_attention_kernel(q, k, v, start, n_valid, scale, window=0,
                          interpret=None, block_q=SPAN_BLOCK_Q,
                          block_k=SPAN_BLOCK_K,
                          resident=SPAN_RESIDENT_TOKENS):
    """Causal (optionally windowed) grouped-query attention of a span.

    ``q (T, Hq, Dh)``: row ``t`` holds position ``start + t``; ``k``/``v
    (S, Hkv, Dh)``: row ``s`` holds position ``s``; kv head ``g`` serves
    query heads ``[g * group, (g + 1) * group)``.  ``start`` and
    ``n_valid`` are int32 scalars, traced or not; rows ``>= n_valid`` are
    padding (what they return is finite and otherwise unspecified).
    ``scale`` multiplies the f32 scores.  Returns ``(T, Hkv, group, Dh)``
    in q's dtype, as ``masked_attention`` does.
    """
    T, Hq, Dh = q.shape
    S, Hkv, _ = k.shape
    if Dh == 64:
        # half a lane tile: zero lanes add nothing to a score and give
        # zero output lanes, so the same kernel serves the padded heads
        # (the MXU contracts over 128 either way)
        pad = ((0, 0), (0, 0), (0, 64))
        return span_attention_kernel(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), start,
            n_valid, scale, window=window, interpret=interpret,
            block_q=block_q, block_k=block_k, resident=resident)[..., :64]
    group = gqa_group(Hq, Hkv)
    bq, bk = min(int(block_q), T), min(int(block_k), S)
    resident = min(int(resident), S)
    if not span_kernel_eligible(T, S, Dh, bq, bk, resident, min_scores=0):
        raise ValueError(
            f"span_attention: {T} rows x {S} keys x heads of {Dh} do not "
            f"tile into ({bq}, {bk}) blocks of {resident} resident keys")
    if window < 0:
        raise ValueError(f"span_attention: window must be >= 0 "
                         f"(got {window})")
    window = int(window)
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    nkb = S // resident

    def kv_at(h, qi, kb, span_ref):
        if nkb == 1:
            return 0, _div(h, group)
        # a step outside the query tile's live range re-reads the nearest
        # live step's block: an unchanged block index is no DMA
        _, lo, hi = _live_keys(span_ref, qi, T=T, S=S, bq=bq, window=window)
        last = jnp.maximum(_div(hi + _i32(resident - 1), resident) - 1, 0)
        return jnp.clip(kb, _div(lo, resident), last), _div(h, group)

    rows = pl.BlockSpec((bq, Dh), idx32(lambda h, qi, kb, span: (qi, h)))
    keys = pl.BlockSpec((resident, Dh), idx32(kv_at))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hq, T // bq, nkb),
        in_specs=[rows, keys, keys],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, Dh), jnp.float32)],
    )
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)
    kernel = functools.partial(_kernel, scale=np.float32(scale),
                               window=window, T=T, S=S, bq=bq, bk=bk,
                               resident=resident)
    span = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hq * Dh), q.dtype),
        name="span_attention",
        # mxtpu-lint: disable=host-sync (static host flag chosen at
        # trace time — never a device value, nothing to sync)
        interpret=bool(interpret),
        **kw,
    )(span, q.reshape(T, Hq * Dh), k.reshape(S, Hkv * Dh),
      v.reshape(S, Hkv * Dh))
    return out.reshape(T, Hkv, group, Dh)


# K and V of a kv head resident twice over (the pipeline's two buffers)
# are 4 x SPAN_RESIDENT_TOKENS x Dh x 2 B = 4 MiB at 4096 x 128; a tile's
# f32 scores and probabilities 2 x 1 MiB at (512, 512).  Said out loud so
# that a wider head or a larger tile is refused by the compiler here, not
# squeezed under the chip's 16 MiB default
_VMEM_LIMIT = 48 * 1024 * 1024
