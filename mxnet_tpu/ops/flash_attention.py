"""Fused flash attention as a Pallas TPU kernel.

The hot op of the attention family (the reference framework predates
attention; its fused-kernel analog is the cuDNN RNN wrapper,
cudnn_rnn-inl.h — this is the TPU-era equivalent: hand-fused kernels
where stock XLA lowering leaves performance on the table).  Standard
streaming-softmax tiling: the (Sq x Sk) score matrix is never
materialized in HBM; each grid step loads one (block_q x d) Q tile and
one (block_k x d) K/V tile into VMEM, updates running max / sum-exp /
accumulator scratch, and writes the normalized output once on the last
K step.  MXU does the two matmuls per tile; accumulation is always
float32 regardless of input dtype.

Backward is a custom VJP with two more Pallas kernels (dQ, and dK/dV)
recomputing probabilities from the saved log-sum-exp — O(S) memory.
The log-sum-exp is also exposed as a differentiable output so ring
attention (parallel/ring_attention.py) can stream-combine per-shard
flash results with correct gradients.

``q_offset``/``k_offset`` shift the positions used by the causal mask,
which is what lets one kernel serve both local attention and one ring
step (global positions = shard offset + local positions).

``interpret=True`` (automatic off-TPU) runs the same kernels through the
Pallas interpreter so tests exercise identical code paths on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_util
from .pallas_util import idx32

__all__ = ["flash_attention", "flash_eligible", "gqa_group"]

# np.float32, not a Python float: inside Mosaic-lowered kernel bodies a
# bare Python float is a weak float64 constant, and Mosaic has no
# f64->f32 cast — the kernel would fail TPU lowering (caught by
# tests/test_perf_contract.py's cross-platform lowering gate)
_NEG_INF = np.float32(-1e30)
_ZERO = np.float32(0.0)
_TINY = np.float32(1e-30)


def _fit_block(S, target):
    """Largest block <= target that divides S (halving — keeps the
    lane/sublane alignment of power-of-two targets)."""
    b = max(1, min(target, S))
    while b > 1 and S % b:
        b //= 2
    return b


def _block_sizes(Sq, Sk, block_q, block_k):
    """Resolve requested block sizes against the sequence lengths.
    Requested sizes are UPPER BOUNDS: measured on v5e, (512, 512) tiles
    run the fwd+bwd step ~4.6x faster than (128, 128) at S=2k (VMEM
    residency amortizes the HBM streams), so callers default high and
    this shrinks to fit shorter or non-multiple sequences.

    A fit that collapses below BOTH the request and MXU scale (e.g. 8
    for S=1000) would trip Mosaic's row-block tiling constraint or crawl
    through a 100x larger grid; the auto path pre-gates such shapes via
    :func:`flash_eligible`, and explicit ``impl="flash"`` callers get an
    actionable error instead of a degenerate kernel.  Deliberate small
    explicit blocks (tests, tiny shapes) stay allowed: the guard only
    fires when the fit shrank BELOW what the caller asked for."""
    bq, bk = _fit_block(Sq, block_q), _fit_block(Sk, block_k)
    if ((bq != Sq and bq < min(block_q, 128))
            or (bk != Sk and bk < min(block_k, 128))):
        raise ValueError(
            f"flash_attention: seq lens ({Sq}, {Sk}) admit no MXU-scale "
            f"block <= requested ({block_q}, {block_k}); fitted "
            f"({bq}, {bk}) — pad the sequence or pass explicit block "
            f"sizes that divide it")
    return bq, bk


def flash_eligible(Sq, Sk, block_q=512, block_k=512):
    """Whether the fused kernel is worth using for these sequence
    lengths: the fitted blocks must either cover the whole (short)
    sequence or stay MXU-scale (>= 128) — a degenerate fitted block
    (e.g. 8 for S=1000) would crawl; callers fall back to dense XLA."""
    bq, bk = _fit_block(Sq, block_q), _fit_block(Sk, block_k)
    return (bq == Sq or bq >= 128) and (bk == Sk or bk >= 128)


# ~16 MB VMEM per v5e core; leave headroom for Mosaic's own temporaries
_VMEM_BUDGET = 12 * 1024 * 1024


def _vmem_bytes(bq, bk, D, H, itemsize=4, Hkv=None):
    """Conservative per-grid-step VMEM footprint of the kernels: Q-class
    tiles (q, do) + K-class tiles (k, v, + pipelining slack), all
    double-buffered in the INPUT dtype (``itemsize`` — the kernels keep
    matmul operands native, so bf16 tiles are half the size), plus f32
    accumulator scratch and the f32 score tile.  An estimate, not
    Mosaic's allocator — it only needs to stop the block autofit from
    requesting tiles that cannot possibly fit."""
    Hf = 1 if H is None else H
    Hk = Hf if Hkv is None else Hkv                  # GQA: fewer kv heads
    tile = lambda blk, h: 2 * blk * h * D * itemsize  # double-buffered
    return (2 * tile(bq, Hf) + 3 * tile(bk, Hk)
            + 2 * Hf * max(bq, bk) * D * 4           # acc/dk/dv scratch
            + bq * bk * 4)                           # score tile


def _fit_vmem(bq, bk, Sq, Sk, D, H, itemsize=4, Hkv=None):
    """Halve the larger block (never below 128 or the whole-sequence
    tile) until the estimated footprint fits the VMEM budget.  The 512
    default was benchmarked on bhsd D=64 where it fits easily; bshd
    blocks span ALL heads, so high-H configs must scale back or Mosaic
    dies with an opaque allocation failure mid-train."""
    def shrinkable(b, S):
        return b > 128 and b == _fit_block(S, b)     # stays a divisor
    while _vmem_bytes(bq, bk, D, H, itemsize, Hkv) > _VMEM_BUDGET:
        if bk >= bq and shrinkable(bk, Sk):
            bk //= 2
        elif shrinkable(bq, Sq):
            bq //= 2
        elif shrinkable(bk, Sk):
            bk //= 2
        else:
            break                                    # floor: let Mosaic try
    # The floor zone (12-16 MB estimated) is left to Mosaic — the
    # estimate is conservative and small overshoots usually fit.  Past
    # physical VMEM the allocation CANNOT succeed; fail with the config
    # instead of Mosaic's opaque allocation error mid-train.
    if _vmem_bytes(bq, bk, D, H, itemsize, Hkv) > 16 * 1024 * 1024:
        raise ValueError(
            f"flash_attention: no block config fits VMEM (floor "
            f"block_q={bq}, block_k={bk} needs "
            f"~{_vmem_bytes(bq, bk, D, H, itemsize, Hkv) >> 20} MB for "
            f"D={D}, H={H}, kv_heads={Hkv}); use layout='bhsd' (per-head "
            f"tiles) or fall back to dense attention (impl='xla')")
    return bq, bk


def _mask_for(i, j, bq, bk, causal, qo, ko, window=0):
    """Score mask for Q tile i vs K tile j (True = keep); qo/ko are
    global position offsets (ring-step shards), possibly traced.
    ``window`` > 0 adds sliding-window locality: query q attends keys in
    (q - window, q] — Mistral-class local attention.  Tiles fully
    outside the band skip their COMPUTE (the FLOPs drop to
    O(S * window)); the grid still visits and fetches every K/V tile,
    so HBM traffic remains O(S^2 / bk) block fetches."""
    if not causal and not window:
        return None
    q_pos = qo + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ko + j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        keep = q_pos >= k_pos
        if window:
            keep = jnp.logical_and(keep, q_pos - k_pos < window)
        return keep
    # bidirectional window: exactly the symmetric band |q - k| < window
    return jnp.logical_and(q_pos - k_pos < window, k_pos - q_pos < window)


def _tile_live(i, j, bq, bk, causal, qo, ko, window=0):
    """Decorator: runs the tile body only when the (i, j) tile overlaps
    the live mask region — above-diagonal tiles (causal) and tiles
    entirely outside the sliding-window band contribute nothing, and
    skipping them is where the causal-FLOPs halving and the window's
    O(S * window) bound come from.  Unmasked bodies run
    unconditionally."""
    if not causal and not window:
        return lambda body: body()
    q_lo = qo + i * bq                 # first/last q position of the tile
    q_hi = q_lo + (bq - 1)
    k_lo = ko + j * bk
    k_hi = k_lo + (bk - 1)
    live = True
    if causal:
        live = jnp.logical_and(live, q_hi >= k_lo)
    if window:
        # any (q, k) in the tile with q - k < window (causal band) or
        # |q - k| < window (bidirectional)
        live = jnp.logical_and(live, q_lo - k_hi < window)
        if not causal:
            live = jnp.logical_and(live, k_lo - q_hi < window)
    return pl.when(live)


# -- forward ------------------------------------------------------------------
#
# Layout strategy (Mosaic tiling rule: the last two dims of every block
# must divide (8, 128) or equal the array dims):
#
# - BHSD: inputs flattened to (BH, S, D); grid (BH, nq, nk); blocks
#   (1, blk, D) — last two dims (blk, D) legal.  One head per grid row.
# - BSHD (sequence-major): the array stays (B, S, H, D) — blocks must
#   span the FULL (H, D) trailing dims to be legal, so the grid is
#   (B, nq, nk) and the kernel loops the (static, unrolled) head axis,
#   slicing each (blk, H, D) VMEM tile per head.  All head shuffling
#   happens in VMEM/registers: zero HBM activation transposes, which is
#   the point of the layout.
#
# Per-row tensors (lse/delta/dlse) are (BH, 1, S) [bhsd] or (B, H, S)
# [bshd] so their blocks' trailing dims can be 'equal' to the array's.


def _heads(H):
    return [None] if H is None else list(range(H))


def gqa_group(Hq, Hkv):
    """Validated grouped-query factor: q heads per shared K/V head.
    The single source of the 'multiple of kv heads' contract — every
    GQA entry point (kernel, op, ring, ulysses) validates through
    here so zero/non-multiple head counts fail identically."""
    if Hkv <= 0 or Hq % Hkv:
        raise ValueError(
            f"grouped-query attention: q heads ({Hq}) must be a "
            f"multiple of kv heads ({Hkv})")
    return Hq // Hkv


def _kv(h, group):
    """KV head for q-head ``h``: grouped-query attention maps ``group``
    consecutive q heads onto one shared K/V head (group == 1 = MHA)."""
    return h if h is None or group == 1 else h // group


def _load(ref, h):
    """(blk, D) tile in the INPUT dtype: 3D block (1, blk, D), or head
    ``h`` of a 4D (1, blk, H, D) block (static sublane index).

    No f32 upcast here: the MXU's fast path is bf16 x bf16 with float32
    accumulation (``preferred_element_type`` on every dot) — upcasting
    the operands would run the matmuls at the ~4x slower f32 MXU rate
    while gaining nothing the f32 accumulator doesn't already give."""
    x = ref[0]
    if h is not None:
        x = x[:, h, :]
    return x


def _store(ref, h, val):
    if h is None:
        ref[0] = val
    else:
        ref[0, :, h, :] = val


def _row(ref, h):
    """(blk,) row from a (1, 1, blk) [bhsd] or (1, H, blk) [bshd] block."""
    return ref[0, 0] if h is None else ref[0, h]


def _row_set(ref, h, val):
    if h is None:
        ref[0, 0] = val
    else:
        ref[0, h] = val


def _sget(ref, h):
    """Scratch slab: whole ref (bhsd) or leading-index ``h`` (bshd)."""
    return ref[...] if h is None else ref[h]


def _sset(ref, h, val):
    if h is None:
        ref[...] = val
    else:
        ref[h] = val


def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc, m_sc, l_sc, *, scale, causal, bq, bk, nk, H,
                window=0, group=1):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    i = pl.program_id(1)

    @_tile_live(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0], window)
    def _():
        mask = _mask_for(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0],
                         window)
        for h in _heads(H):
            q = _load(q_ref, h)
            k = _load(k_ref, _kv(h, group))
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, _NEG_INF)

            m_prev = _sget(m_sc, h)[:, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_cur[:, None])
            if mask is not None:
                # without this, a fully-masked row (m_cur == _NEG_INF)
                # would get p == exp(0) == 1 for every masked entry
                p = jnp.where(mask, p, _ZERO)
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = _sget(l_sc, h)[:, 0] * alpha + jnp.sum(p, axis=-1)
            v = _load(v_ref, _kv(h, group))
            # p cast DOWN to v's dtype so a bf16 input keeps the PV
            # matmul on the fast MXU path (f32 @ bf16 would promote v
            # and run the slow f32 pass); accumulation stays f32
            _sset(acc, h, _sget(acc, h) * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32))
            _sset(m_sc, h, m_cur[:, None])
            _sset(l_sc, h, l_cur[:, None])

    @pl.when(j == nk - 1)
    def _():
        for h in _heads(H):
            l_row = _sget(l_sc, h)[:, 0]
            valid = l_row > _ZERO     # False only for fully-masked rows
            l_fin = jnp.maximum(l_row, _TINY)
            _store(o_ref, h,
                   jnp.where(valid[:, None], _sget(acc, h) / l_fin[:, None],
                             _ZERO).astype(o_ref.dtype))
            _row_set(lse_ref, h,
                     jnp.where(valid, _sget(m_sc, h)[:, 0] + jnp.log(l_fin),
                               _NEG_INF))


def _scalar_spec():
    return pl.BlockSpec((1, 1), idx32(lambda b, x, y: (0, 0)),
                        memory_space=pltpu.SMEM)


def _dims(q, k):
    """(BH, Sq, Sk, D, H) for a 3D (BH, S, D) [BHSD, flattened] or 4D
    (B, S, H, D) [BSHD] tensor pair.  H is None in the 3D case."""
    if q.ndim == 3:
        BH, Sq, D = q.shape
        return BH, Sq, k.shape[1], D, None
    B, Sq, H, D = q.shape
    return B * H, Sq, k.shape[1], D, H


def _seq_spec(blk, D, H, pick):
    """Block spec for a Q/K/V/dO-class tensor: BHSD (H=None) gets a
    (blk, D) tile of the flattened (BH, S, D) array per grid step; BSHD
    gets a (blk, H, D) tile spanning ALL heads (Mosaic requires full
    trailing (H, D) dims; the kernel head-loops in VMEM).  ``pick``
    selects which grid axis is this tensor's sequence block."""
    if H is None:
        return pl.BlockSpec((1, blk, D), idx32(lambda *g: (g[0], pick(g), 0)))
    return pl.BlockSpec((1, blk, H, D),
                        idx32(lambda *g: (g[0], pick(g), 0, 0)))


def _out_shape(BH, S, D, H, dtype):
    if H is None:
        return jax.ShapeDtypeStruct((BH, S, D), dtype)
    return jax.ShapeDtypeStruct((BH // H, S, H, D), dtype)


def _row_spec(blk, H, pick):
    """Block spec for an lse/delta-class per-row tensor, stored
    (BH, 1, S) [bhsd] or (B, H, S) [bshd]: Mosaic requires the last two
    block dims to divide (8, 128) or equal the array dims — a (1, blk)
    block of a 2D (BH, S) array fails that whenever BH > 1, so the row
    tensors carry a middle dim the block can be 'equal' on."""
    if H is None:
        return pl.BlockSpec((1, 1, blk), idx32(lambda *g: (g[0], 0, pick(g))))
    return pl.BlockSpec((1, H, blk), idx32(lambda *g: (g[0], 0, pick(g))))


def _row_shape(BH, S, H):
    if H is None:
        return (BH, 1, S)
    return (BH // H, H, S)


def _params(interpret):
    """Grid semantics: batch*head and q-block rows are independent
    (PARALLEL -> Mosaic may pipeline/reorder them); the k-block axis
    carries the running-softmax scratch state and must stay sequential
    (ARBITRARY).  Unsupported by the interpreter backend."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _fwd(q, k, v, qo, ko, scale, causal, bq, bk, interpret, window=0):
    BH, Sq, Sk, D, H = _dims(q, k)
    nq, nk = Sq // bq, Sk // bk
    # grouped-query attention (bshd only): K/V may carry fewer heads
    Hkv = None if H is None else k.shape[2]
    group = 1 if H is None else H // Hkv
    kernel = functools.partial(_fwd_kernel, scale=np.float32(scale),
                               causal=causal, bq=bq, bk=bk, nk=nk, H=H,
                               window=window, group=group)
    qi = lambda g: g[1]
    ki = lambda g: g[2]
    grid0 = BH if H is None else BH // H
    sc = (lambda *dims: pltpu.VMEM(dims, jnp.float32)) if H is None else (
        lambda *dims: pltpu.VMEM((H,) + dims, jnp.float32))
    o, lse = pl.pallas_call(
        kernel,
        grid=(grid0, nq, nk),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            _seq_spec(bq, D, H, qi),
            _seq_spec(bk, D, Hkv, ki),
            _seq_spec(bk, D, Hkv, ki),
        ],
        out_specs=[
            _seq_spec(bq, D, H, qi),
            _row_spec(bq, H, qi),
        ],
        out_shape=[
            _out_shape(BH, Sq, D, H, q.dtype),
            jax.ShapeDtypeStruct(_row_shape(BH, Sq, H), jnp.float32),
        ],
        scratch_shapes=[
            sc(bq, D),
            sc(bq, 1),
            sc(bq, 1),
        ],
        interpret=interpret,
        **_params(interpret),
    )(qo, ko, q, k, v)
    return o, lse.reshape(BH, Sq)


# -- backward -----------------------------------------------------------------

def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dlse_ref, dq_ref, dq_acc, *, scale, causal,
                   bq, bk, nk, H, window=0, group=1):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    i = pl.program_id(1)

    @_tile_live(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0], window)
    def _():
        mask = _mask_for(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0],
                         window)
        for h in _heads(H):
            q = _load(q_ref, h)
            k = _load(k_ref, _kv(h, group))
            v = _load(v_ref, _kv(h, group))
            do = _load(do_ref, h)
            lse = _row(lse_ref, h)
            delta = _row(delta_ref, h)
            dlse = _row(dlse_ref, h)

            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if mask is not None:
                p = jnp.where(mask, p, _ZERO)  # fully-masked: lse=_NEG_INF
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            # ds from the o path (p*(dp - delta)) and the lse output (p*dlse)
            ds = p * (dp - delta[:, None] + dlse[:, None]) * scale
            # ds cast down to the input dtype for the same MXU-path
            # reason as p in the forward (standard flash bwd recipe)
            _sset(dq_acc, h, _sget(dq_acc, h) + jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32))

    @pl.when(j == nk - 1)
    def _():
        for h in _heads(H):
            _store(dq_ref, h, _sget(dq_acc, h).astype(dq_ref.dtype))


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, bq, bk, nq, H, window=0, group=1):
    i = pl.program_id(2)  # q-block index (inner loop)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    j = pl.program_id(1)  # k-block index (outer)

    @_tile_live(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0], window)
    def _():
        mask = _mask_for(i, j, bq, bk, causal, qo_ref[0, 0], ko_ref[0, 0],
                         window)
        for h in _heads(H):
            hk = _kv(h, group)
            q = _load(q_ref, h)
            k = _load(k_ref, hk)
            v = _load(v_ref, hk)
            do = _load(do_ref, h)
            lse = _row(lse_ref, h)
            delta = _row(delta_ref, h)
            dlse = _row(dlse_ref, h)

            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])
            if mask is not None:
                p = jnp.where(mask, p, _ZERO)  # fully-masked: lse=_NEG_INF
            # grouped-query attention: every q head of the group adds
            # into the SAME kv-head accumulator slab — the dK/dV sum
            # over the group happens right here in VMEM
            _sset(dv_acc, hk, _sget(dv_acc, hk) + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None] + dlse[:, None]) * scale
            _sset(dk_acc, hk, _sget(dk_acc, hk) + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

    @pl.when(i == nq - 1)
    def _():
        for hk in _heads(H if H is None else H // group):
            _store(dk_ref, hk, _sget(dk_acc, hk).astype(dk_ref.dtype))
            _store(dv_ref, hk, _sget(dv_acc, hk).astype(dv_ref.dtype))


def _bwd(scale, causal, bq, bk, interpret, window, res, g):
    q, k, v, qo, ko, o, lse = res
    do, dlse_in = g
    BH, Sq, Sk, D, H = _dims(q, k)
    nq, nk = Sq // bq, Sk // bk

    # do stays in the kernels' input dtype (bf16 on TPU): the dot with v
    # runs the fast MXU pass with f32 accumulation; only the rowwise
    # delta reduction upcasts (outside the kernels, O(S) not O(S^2))
    do = do.astype(q.dtype)
    dlse = (jnp.zeros_like(lse) if dlse_in is None
            else dlse_in.astype(jnp.float32))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if H is not None:
        # (B, Sq, H) -> (B, H, Sq): the kernels' row layout; tiny (no D)
        delta = jnp.moveaxis(delta, 1, 2)
    # row tensors carry a middle dim for Mosaic (see _row_spec)
    row_shape = _row_shape(BH, Sq, H)
    lse = lse.reshape(row_shape)
    delta = delta.reshape(row_shape)
    dlse = dlse.reshape(row_shape)

    grid0 = BH if H is None else BH // H
    Hkv = None if H is None else k.shape[2]
    group = 1 if H is None else H // Hkv
    sc = (lambda *dims: pltpu.VMEM(dims, jnp.float32)) if H is None else (
        lambda *dims: pltpu.VMEM((H,) + dims, jnp.float32))
    qi = lambda g: g[1]
    ki = lambda g: g[2]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=np.float32(scale),
                          causal=causal, bq=bq, bk=bk, nk=nk, H=H,
                          window=window, group=group),
        grid=(grid0, nq, nk),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            _seq_spec(bq, D, H, qi),
            _seq_spec(bk, D, Hkv, ki),
            _seq_spec(bk, D, Hkv, ki),
            _seq_spec(bq, D, H, qi),
            _row_spec(bq, H, qi),
            _row_spec(bq, H, qi),
            _row_spec(bq, H, qi),
        ],
        out_specs=_seq_spec(bq, D, H, qi),
        out_shape=_out_shape(BH, Sq, D, H, q.dtype),
        scratch_shapes=[sc(bq, D)],
        interpret=interpret,
        **_params(interpret),
    )(qo, ko, q, k, v, do, lse, delta, dlse)

    qj = lambda g: g[2]
    kj = lambda g: g[1]
    sc_kv = sc if H is None else (
        lambda *dims: pltpu.VMEM((Hkv,) + dims, jnp.float32))
    BHkv = BH if H is None else (BH // H) * Hkv
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=np.float32(scale),
                          causal=causal, bq=bq, bk=bk, nq=nq, H=H,
                          window=window, group=group),
        grid=(grid0, nk, nq),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            _seq_spec(bq, D, H, qj),
            _seq_spec(bk, D, Hkv, kj),
            _seq_spec(bk, D, Hkv, kj),
            _seq_spec(bq, D, H, qj),
            _row_spec(bq, H, qj),
            _row_spec(bq, H, qj),
            _row_spec(bq, H, qj),
        ],
        out_specs=[
            _seq_spec(bk, D, Hkv, kj),
            _seq_spec(bk, D, Hkv, kj),
        ],
        out_shape=[
            _out_shape(BHkv, Sk, D, Hkv, k.dtype),
            _out_shape(BHkv, Sk, D, Hkv, v.dtype),
        ],
        scratch_shapes=[sc_kv(bk, D), sc_kv(bk, D)],
        interpret=interpret,
        **_params(interpret),
    )(qo, ko, q, k, v, do, lse, delta, dlse)
    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, qo, ko, scale, causal, bq, bk, interpret, window):
    return _fwd(q, k, v, qo, ko, scale, causal, bq, bk, interpret, window)


def _flash_fwd(q, k, v, qo, ko, scale, causal, bq, bk, interpret, window):
    o, lse = _fwd(q, k, v, qo, ko, scale, causal, bq, bk, interpret, window)
    return (o, lse), (q, k, v, qo, ko, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, q_offset=0, k_offset=0, return_lse=False,
                    interpret=None, layout="bhsd", window=0):
    """Fused multi-head attention: softmax(QK^T * scale) V.

    ``layout="bhsd"``: q (B, H, Sq, D), k/v (B, H, Sk, D) — the
    classic shape.  ``layout="bshd"``: q (B, Sq, H, D), k/v
    (B, Sk, H, D) — sequence-major, fed to the kernel with the head dim
    INDEXED in the block specs, so activations coming from a
    (B, S, D)-major transformer stack need no HBM transpose on the way
    in or out (the per-layer BSHD<->BHSD shuffles are the only
    activation transposes in the GPT train step's HLO).  Differentiable
    (custom VJP) either way; output matches the input layout.

    ``block_q``/``block_k`` are upper bounds; they shrink (by
    halving) to fit the sequence lengths.  ``window`` > 0 enables
    sliding-window (local) attention: each query sees keys within
    ``window`` positions (causal: the trailing band (q-window, q];
    bidirectional: |q-k| < window).  Tiles fully outside the band skip
    their matmuls — attention FLOPs drop to O(S * window) — though the
    grid still streams every K/V tile, so HBM traffic stays O(S^2/bk).
    ``q_offset``/``k_offset`` shift the causal-mask positions (may be
    traced values — used for ring-attention shards).  With
    ``return_lse`` the per-row log-sum-exp (B, H, Sq) float32 is also
    returned (differentiable).  Off-TPU the kernels run in the Pallas
    interpreter unless ``interpret`` is explicitly set.
    """
    if window < 0:
        raise ValueError(
            f"flash_attention: window must be >= 0 (got {window}); a "
            "negative band would mask every score")
    if layout == "bshd":
        B, Sq, H, D = q.shape
        Sk, Hkv = k.shape[1], k.shape[2]
    else:
        B, H, Sq, D = q.shape
        Sk, Hkv = k.shape[2], k.shape[1]
    if Hkv != H:
        # grouped-query / multi-query attention: `group` consecutive q
        # heads share one K/V head
        gqa_group(H, Hkv)
        if v.shape != k.shape:
            raise ValueError("flash_attention: k and v shapes must match")
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    bq, bk = _block_sizes(Sq, Sk, block_q, block_k)
    bq, bk = _fit_vmem(bq, bk, Sq, Sk, D,
                       H if layout == "bshd" else None,
                       itemsize=jnp.dtype(q.dtype).itemsize,
                       Hkv=Hkv if layout == "bshd" else None)

    if layout == "bshd":
        qf, kf, vf = q, k, v              # native 4D, no data movement
        # (GQA handled natively: the kernels map q heads onto kv heads)
    else:
        if Hkv != H:
            # the flattened (BH, S, D) layout has no head axis for the
            # kernel to group on — expand K/V instead (correct, but the
            # traffic saving needs layout='bshd', where GQA is native)
            k = jnp.repeat(k, H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
        qf = q.reshape(B * H, Sq, D)
        kf = k.reshape(B * H, Sk, D)
        vf = v.reshape(B * H, Sk, D)
    if not causal and not window:
        # no mask consumes positions, so the offsets are inert — drop
        # them to constants.  More than hygiene: ring attention passes
        # axis_index-derived offsets, and XLA's SPMD partitioner
        # refuses a partition-id-rooted operand threaded into the
        # kernel call inside the ring's scan (PartitionId UNIMPLEMENTED
        # on CPU) when nothing in the kernel reads it.
        q_offset, k_offset = 0, 0
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    ko = jnp.asarray(k_offset, jnp.int32).reshape(1, 1)
    o, lse = _flash(qf, kf, vf, qo, ko, scale, bool(causal), bq, bk,
                    bool(interpret), int(window))
    if layout != "bshd":
        o = o.reshape(B, H, Sq, D)
    if return_lse:
        return o, lse.reshape(B, H, Sq)
    return o
