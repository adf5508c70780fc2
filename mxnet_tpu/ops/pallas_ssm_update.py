"""The single-token state-space update as a Pallas TPU kernel.

A decode step of a state-space layer reads each live row's state
(H x P x N float32, 2 MiB at 64 x 64 x 128) and writes it back:

    S = exp(dA) * S + (dt * x) (x) B          y = S C

The states of every layer and request live in ONE stacked pool
``(layers, slots, H, P, N)``.  The grid walks ``(row, head group)``;
the row's slot is scalar-prefetched, so the block's DMA address is
``(layer, slots[b], g, 0, 0)`` and the pool is aliased to the output:
each live state crosses HBM once in and once out, and nothing else of
the pool moves.  A ``pool[layer]`` slice in front of a custom call, or
a gather and a scatter around an XLA fusion, would copy the layer's
pool or the live states two more times (PERF.md, PR 27's lesson).

Layout inside the kernel: a head's state tile is (P, N) with N on the
lanes.  ``dt * x`` arrives transposed, (P, heads), so that a head's
column broadcasts along the lanes with no relayout, and ``y`` leaves
the same way; the caller transposes both (a few KB).

``B`` and ``C`` belong to state GROUPS (head ``h`` reads group ``h //
(H / G)``).  A grid step takes the heads of whole groups, or a part of
one group's, and its block of the two vectors holds exactly the groups
its heads read, so inside the kernel a head's group is a static row.

A dead row, one that names the null slot 0 (a bucket's padding), does
no work: the body runs under ``pl.when(slot != 0)``, and every block
index of a dead row is its SOURCE row's, the next live row (the last
one, behind it) at the head step the live row's own steps meet it on.
Consecutive grid steps with the same block index copy nothing in or
out, so a dead row moves no state, no per-row vector and nothing of
``y``: the kernel's cost follows the live rows, not the bucket, and the
null slot is not written (unless no row at all is live).  The grid
walks its steps in order for this, so both axes are ``arbitrary``.
Dead rows' ``y`` is exactly 0.
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

__all__ = ["ssm_update_kernel", "KERNEL_NAME"]

# the kernel's name in a device trace (benchmark readers look for it)
KERNEL_NAME = "ssm_state_update"

# heads of one row a grid step moves: 64 x (64, 128) float32 = 2 MiB in
# and 2 MiB out, double-buffered 8 MiB of VMEM.  On one v5e chip at 64
# rows x 36 layers: 16 heads a step 64 % of the HBM peak, 32: 69 %,
# 64: 71 % (my chip run, PR 29)
HEADS_PER_STEP = 64


def _kernel(slots_ref, src_ref, pin_ref, pool_ref, xdt_ref, dec_ref,
            b_ref, c_ref, y_ref, pool_out_ref, *, hb, gb):
    del src_ref, pin_ref                # consumed by the index maps

    @pl.when(slots_ref[pl.program_id(0)] != 0)
    def _():
        # the step's groups, (1, N) each; its heads divide evenly among them
        Bv = [b_ref[0, j:j + 1] for j in range(gb)]
        Cv = [c_ref[0, j:j + 1] for j in range(gb)]
        for h in range(hb):
            j = h * gb // hb
            S = pool_ref[0, 0, h]           # (P, N)
            col = xdt_ref[0, 0, :, h:h + 1]         # (P, 1)
            a = dec_ref[0, 0, :, h:h + 1]           # (1, 1)
            S = a * S + col * Bv[j]
            pool_out_ref[0, 0, h] = S
            y_ref[0, 0, :, h:h + 1] = jnp.sum(S * Cv[j], axis=-1,
                                              keepdims=True)


def _sources(slots, G):
    """Each row's source row, and the head step a dead row is pinned to
    (-1 for a live row): a dead row takes the next live row at its first
    step, so that row's blocks arrive while the live row in front still
    computes, or, behind the last live row, that row at its last step.
    Either way a dead step's block indices equal those of a live step
    beside it."""
    B = slots.shape[0]
    live = slots != 0
    rows = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live, rows, B), reverse=True)
    prv = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(nxt < B, nxt, jnp.maximum(prv, 0))
    pin = jnp.where(live, -1, jnp.where(nxt < B, 0, G - 1))
    return src, pin.astype(jnp.int32)


@hot_path
def ssm_update_kernel(pool, layer, slots, x, dt, dA, Bm, Cm, D,
                      heads_per_step=None, interpret=None):
    """Same contract as ``ops.ssm.ssm_state_update``: pool (L, S, H, P,
    N) float32 with the static ``layer``; slots (B,) int32; x (B, H,
    P); dt, dA (B, H); Bm, Cm (B, G, N) or, one group, (B, N); D (H,).
    Returns ``(y (B, H, P) in x's dtype, the pool)``."""
    L, S, H, P, N = pool.shape
    B = x.shape[0]
    if not 0 <= layer < L:
        raise ValueError(f"ssm_update: layer {layer} outside the pool's "
                         f"{L} layers")
    if heads_per_step is None:
        heads_per_step = HEADS_PER_STEP
    hb = min(int(heads_per_step), H)
    if H % hb:
        raise ValueError(f"ssm_update: {H} heads do not divide into "
                         f"groups of {hb}")
    G = H // hb
    # heads of one state group, and how a step's heads meet the groups:
    # ``gb`` whole groups a step, or a group over ``spb`` steps
    R = H // (Bm.shape[1] if Bm.ndim == 3 else 1)
    if hb % R and R % hb:
        raise ValueError(f"ssm_update: a step's {hb} heads neither hold "
                         f"whole groups of {R} nor divide one")
    gb, spb = max(1, hb // R), max(1, R // hb)
    nblk = H // (gb * R)                # blocks of groups a row
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    f32 = jnp.float32
    xf = x.astype(f32)
    # (B, H, P) -> (B, G, P, hb): a head's dt*x is one lane-column
    xdt = (xf * dt.astype(f32)[..., None]).reshape(B, G, hb, P)
    xdt = jnp.swapaxes(xdt, 2, 3)
    dec = jnp.exp(dA.astype(f32)).reshape(B, G, 1, hb)
    b3 = Bm.astype(f32).reshape(B * nblk, gb, N)
    c3 = Cm.astype(f32).reshape(B * nblk, gb, N)

    slots = jnp.asarray(slots, jnp.int32)
    src, pin = _sources(slots, G)

    def at(fn):
        # a dead row's step reads its source row at its pinned head step
        return idx32(lambda b, g, sl, sr, pn: fn(
            sl, sr[b], jnp.where(pn[b] < 0, g, pn[b])))

    per_state = at(lambda sl, r, g: (layer, sl[r], g, 0, 0))
    per_row = at(lambda sl, r, g: (r, g, 0, 0))
    if nblk == 1:                       # every step reads the row's one
        per_vec = at(lambda sl, r, g: (r, 0, 0))
    else:
        # lax.div: jnp's floor division does not lower here under x64
        per_vec = at(lambda sl, r, g: (
            r * nblk + jax.lax.div(g, jnp.int32(spb)), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, G),
        in_specs=[
            pl.BlockSpec((1, 1, hb, P, N), per_state),
            pl.BlockSpec((1, 1, P, hb), per_row),
            pl.BlockSpec((1, 1, 1, hb), per_row),
            pl.BlockSpec((1, gb, N), per_vec),
            pl.BlockSpec((1, gb, N), per_vec),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, P, hb), per_row),
            pl.BlockSpec((1, 1, hb, P, N), per_state),
        ],
    )
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    yT, pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb, gb=gb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, G, P, hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands 0-2 are scalar-prefetched; the pool is operand 3
        input_output_aliases={3: 1},
        name=KERNEL_NAME,
        # mxtpu-lint: disable=host-sync (static host flag chosen at
        # trace time, never a device value)
        interpret=bool(interpret),
        **kw,
    )(slots, src, pin, pool, xdt, dec, b3, c3)
    y = jnp.swapaxes(yT, 2, 3).reshape(B, H, P)
    y = y + xf * D.astype(f32)[None, :, None]
    # a dead row's y block was never written
    y = jnp.where((slots != 0)[:, None, None], y, 0)
    return y.astype(x.dtype), pool
