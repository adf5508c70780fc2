"""State-space (Mamba-2 / SSD) sequence mixing for the serving programs.

The recurrence, per head ``h`` with state ``S`` (P x N):

    S_t = a_t S_{t-1} + dt_t * x_t (x) B_t        a_t = exp(dA_t) <= 1
    y_t = S_t C_t + D * x_t

``B_t`` and ``C_t`` belong to a state GROUP: with ``G`` groups over ``H``
heads, head ``h`` reads the rows of group ``h // (H / G)``.  Both
functions take them as ``(..., G, N)``, or as ``(..., N)`` where all the
heads share one group.

Two formulations of the SAME arithmetic:

- :func:`ssd_chunked_scan`: the chunked (state-space-dual) form for a
  whole span of positions.  Inside a chunk of ``chunk`` positions the
  outputs are matrix products (a masked, decay-weighted ``C B^T`` times
  ``dt * x``); one state per chunk is carried by a short ``lax.scan``
  over the CHUNKS.  Never a scan over positions.  It starts from a given
  state and returns the state after the last position, so a prompt cut
  into passes carries its state between programs.  A position whose
  ``dt`` is 0 neither decays nor feeds the state: that is how a
  bucket's padding leaves the state alone.
- :func:`ssm_state_update`: one position for each of ``B`` rows whose
  states live in a stacked pool ``(layers, slots, H, P, N)``.  The pool
  is addressed at ``(layer, slot[b])`` (one gather, one scatter over
  the donated stack) and never sliced by layer first: a slice of the
  stack is a copy of that layer's whole pool (PERF.md, PR 27).  On a
  TPU the update runs as the Mosaic kernel in
  ``ops/pallas_ssm_update.py`` (slots scalar-prefetched, the pool
  aliased in place; a row that names the null slot 0 does no work
  there and its ``y`` is 0, where this XLA form computes it and writes
  slot 0).

The causal depthwise convolution in front of the scan keeps the last
``K - 1`` rows of its input between programs (:func:`causal_conv`), in
a pool stacked like the states' (:func:`conv_rows_shape`); a decode
step reads and writes a row's at ``(layer, slot[b])``
(:func:`conv_state_step`: on a TPU the Mosaic kernel in
``ops/pallas_conv_step.py`` where the pool holds lane rows).

Everything that enters a state is float32; the large products take
bfloat16 operands with float32 accumulation, like every other matmul of
the serving programs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_util

__all__ = ["causal_conv", "ssd_chunked_scan", "ssm_state_update",
           "softplus", "conv_rows_shape", "conv_slot_rows",
           "conv_pool_rows", "conv_state_step", "CONV_LANES"]

_F32 = jnp.float32

# the convolution rows' lane rows, and the sublane tile a slot's lane
# rows are padded to (the pool's tiling in HBM, which a DMA addresses)
CONV_LANES, CONV_SUBLANES = 128, 8


def softplus(x):
    """log(1 + exp(x)) in float32, stable for large x."""
    x = x.astype(_F32)
    return jnp.maximum(x, np.float32(0.0)) + jnp.log1p(
        jnp.exp(-jnp.abs(x)))


def causal_conv(u, prev, weight, bias, n_valid=None):
    """Depthwise causal convolution over a span, then SiLU.

    ``u`` (T, C) is the span's input, ``prev`` (K-1, C) the rows in
    front of it (zeros at the start of a sequence), ``weight`` (C, K)
    with tap ``K-1`` on the current row, ``bias`` (C,).  Returns
    ``(silu(conv) (T, C), rows)`` where ``rows`` (K-1, C) are the input
    rows in front of position ``n_valid`` (default T): what the next
    pass, or the first decode step, starts from.
    """
    T, C = u.shape
    K = weight.shape[1]
    full = jnp.concatenate([prev.astype(u.dtype), u], axis=0)  # (T+K-1, C)
    wf = weight.astype(_F32)
    acc = bias.astype(_F32)[None, :]
    for j in range(K):
        acc = acc + full[j:j + T].astype(_F32) * wf[None, :, j]
    out = (acc * jax.nn.sigmoid(acc)).astype(u.dtype)
    if n_valid is None:
        rows = full[T:]
    else:
        rows = jax.lax.dynamic_slice_in_dim(
            full, jnp.asarray(n_valid, jnp.int32), K - 1, axis=0)
    return out, rows


def conv_rows_shape(K, channels):
    """One slot's K-1 convolution rows as the pool holds them.  Where
    the ``channels`` C are a multiple of 128: ``(R, 128)`` lane rows,
    row ``j`` of the K-1 at lane rows ``[j C / 128, (j + 1) C / 128)``,
    ``R`` = (K-1) C / 128 rounded up to a multiple of 8 (the rows behind
    them stay 0), so that a slot is whole tiles of the pool a DMA can
    address.  Elsewhere the K-1 rows flat, ``((K-1) C,)``."""
    width = (K - 1) * channels
    if channels % CONV_LANES:
        return (width,)
    rows = width // CONV_LANES
    return (-(-rows // CONV_SUBLANES) * CONV_SUBLANES, CONV_LANES)


def conv_slot_rows(x, K, C):
    """Slots' rows as the pool holds them, ``(..., *conv_rows_shape(K,
    C))``, as ``(..., K-1, C)``."""
    lead = x.shape[:x.ndim - len(conv_rows_shape(K, C))]
    flat = x.reshape(lead + (-1,))[..., :(K - 1) * C]
    return flat.reshape(lead + (K - 1, C))


def conv_pool_rows(rows, K, C):
    """``(..., K-1, C)`` as the pool holds a slot's rows."""
    shape = conv_rows_shape(K, C)
    lead = rows.shape[:-2]
    flat = rows.reshape(lead + (-1,))
    pad = int(np.prod(shape)) - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, ((0, 0),) * len(lead) + ((0, pad),))
    return flat.reshape(lead + shape)


def conv_state_step(pool, layer, slots, u, weight, bias, impl=None):
    """One position of the causal convolution for each of B rows, each
    row's K-1 rows in front of it read from and written back to its slot
    of the pool.

    pool (L, S, *conv_rows_shape(K, C)) in the activation dtype, the
    donated stack of every state layer's rows; ``layer`` an int; slots
    (B,) int32 (padded rows name the null slot 0); u (B, C); weight (C,
    K); bias (C,).  Returns ``(silu(conv) (B, C) in u's dtype, pool)``.
    ``impl``: None follows the backend and the pool's layout (the kernel
    in ``ops/pallas_conv_step.py`` on a TPU where the pool holds lane
    rows: a dead row moves nothing there and its output is 0, where this
    XLA form computes it and writes slot 0); ``"jnp"`` forces the XLA
    form, a gather, :func:`causal_conv` and a scatter: the kernel's
    parity oracle.
    """
    if impl not in (None, "jnp"):
        raise ValueError(f"conv_state_step: impl must be None or 'jnp' "
                         f"(got {impl!r})")
    if impl is None and pool.ndim == 4 and pallas_util.on_tpu():
        from .pallas_conv_step import conv_step_kernel

        return conv_step_kernel(pool, layer, slots, u, weight, bias)
    C, K = weight.shape
    # each row is a span of one position in front of its slot's rows
    prev = conv_slot_rows(pool[layer, slots], K, C)
    out, rows = jax.vmap(causal_conv, in_axes=(0, 0, None, None))(
        u[:, None, :], prev.astype(u.dtype), weight, bias)
    pool = pool.at[layer, slots].set(
        conv_pool_rows(rows.astype(pool.dtype), K, C))
    return out[:, 0], pool


def ssd_chunked_scan(x, dt, dA, Bm, Cm, D, state, chunk):
    """Chunked state-space-dual scan of one sequence's span.

    x (T, H, P) in the activation dtype; dt (T, H) float32 step sizes
    (0 at padded positions); dA (T, H) float32 log-decays
    (``-exp(A_log) * dt``, so 0 where dt is 0); Bm, Cm (T, G, N) the
    groups' input and output projections ((T, N): one group); D (H,);
    state (H, P, N) float32 in front of the span.  A span that is not a
    whole number of chunks is padded with positions of ``dt`` 0.
    Returns ``(y (T, H, P) in x's dtype, state after the last position
    (H, P, N) float32)``.
    """
    T0, H, P = x.shape
    N = Bm.shape[-1]
    if Bm.ndim == 3:
        # the groups share nothing: each is the one-group scan over its
        # own heads
        G = Bm.shape[1]
        y, last = jax.vmap(
            lambda *group: ssd_chunked_scan(*group, chunk),
            in_axes=(1, 1, 1, 1, 1, 0, 0), out_axes=(1, 0))(
                x.reshape(T0, G, H // G, P), dt.reshape(T0, G, H // G),
                dA.reshape(T0, G, H // G), Bm, Cm, D.reshape(G, H // G),
                state.reshape(G, H // G, P, N))
        return y.reshape(T0, H, P), last.reshape(H, P, N)
    Q = min(int(chunk), T0)
    if T0 % Q:
        pad = Q - T0 % Q
        x, dt, dA, Bm, Cm = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                             for a in (x, dt, dA, Bm, Cm))
    T = x.shape[0]
    nc = T // Q
    mm = x.dtype                      # matmul operand dtype (bf16 served)
    xc = x.reshape(nc, Q, H, P)
    dtc = dt.astype(_F32).reshape(nc, Q, H)
    cum = jnp.cumsum(dA.astype(_F32).reshape(nc, Q, H), axis=1)
    Bc = Bm.reshape(nc, Q, N)
    Cc = Cm.reshape(nc, Q, N)
    # dt-weighted input, float32 then rounded once for the MXU
    xdt = xc.astype(_F32) * dtc[..., None]                   # (nc,Q,H,P)
    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) xdt_j
    G = jnp.einsum("cin,cjn->cij", Cc, Bc,
                   preferred_element_type=_F32)              # (nc,Q,Q)
    cumh = jnp.swapaxes(cum, 1, 2)                           # (nc,H,Q)
    seg = cumh[..., :, None] - cumh[..., None, :]            # (nc,H,Q,Q)
    tri = (jnp.arange(Q, dtype=jnp.int32)[:, None]
           >= jnp.arange(Q, dtype=jnp.int32)[None, :])
    # exp of the masked difference: above the diagonal cum_i - cum_j is
    # positive and may overflow before the mask would drop it
    L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, np.float32(0.0))),
                  np.float32(0.0))
    M = (G[:, None] * L).astype(mm)                          # (nc,H,Q,Q)
    y = jnp.einsum("chij,cjhp->cihp", M, xdt.astype(mm),
                   preferred_element_type=_F32)
    # each chunk's own contribution to the state at its end
    tail = jnp.exp(cum[:, -1:, :] - cum)                     # (nc,Q,H)
    Sc = jnp.einsum("cjhp,cjn->chpn",
                    (xdt * tail[..., None]).astype(mm), Bc,
                    preferred_element_type=_F32)             # (nc,H,P,N)
    total = jnp.exp(cum[:, -1, :])                           # (nc,H)

    def carry(S, inp):
        Sc_c, tot_c = inp
        return tot_c[:, None, None] * S + Sc_c, S

    last, S_in = jax.lax.scan(carry, state.astype(_F32), (Sc, total))
    # what the state in front of each chunk adds to its outputs
    y = y + jnp.einsum("cin,chpn->cihp", Cc, S_in.astype(mm),
                       preferred_element_type=_F32) \
        * jnp.exp(cum)[..., None]
    y = y + xc.astype(_F32) * D.astype(_F32)[None, None, :, None]
    return y.reshape(T, H, P)[:T0].astype(x.dtype), last


def _per_head(v, H):
    """A group vector ``(B, G, N)`` or ``(B, N)`` as every head of ``H``
    reads it: float32, broadcastable against ``(B, H, P, N)``."""
    v = v.astype(_F32)
    if v.ndim == 2:
        return v[:, None, None, :]
    return jnp.repeat(v, H // v.shape[1], axis=1)[:, :, None, :]


def ssm_state_update(pool, layer, slots, x, dt, dA, Bm, Cm, D, impl=None):
    """One position for each row, states updated in place in the pool.

    pool (L, S, H, P, N) float32, the donated stack of every layer's
    states; ``layer`` a static index; slots (B,) int32 (padded rows
    name the null slot 0); x (B, H, P); dt, dA (B, H) float32; Bm, Cm
    (B, G, N), or (B, N) for one group; D (H,).  Returns ``(y (B, H, P)
    in x's dtype, pool)``.
    ``impl``: None follows the backend (the kernel on a TPU); ``"jnp"``
    forces the XLA form, the kernel's parity oracle.
    """
    if impl not in (None, "jnp"):
        raise ValueError(f"ssm_state_update: impl must be None or 'jnp' "
                         f"(got {impl!r})")
    if impl is None and pallas_util.on_tpu():
        from .pallas_ssm_update import ssm_update_kernel

        return ssm_update_kernel(pool, int(layer), slots, x, dt, dA,
                                 Bm, Cm, D)
    with jax.named_scope("ssm_state_update"):
        S = pool[layer, slots]                               # (B,H,P,N)
        H = S.shape[1]
        xdt = x.astype(_F32) * dt.astype(_F32)[..., None]
        S = (jnp.exp(dA.astype(_F32))[..., None, None] * S
             + xdt[..., None] * _per_head(Bm, H))
        # an elementwise product and a sum, not an einsum: a float32
        # matmul at default precision would round the state to bf16
        y = jnp.sum(S * _per_head(Cm, H), axis=-1)
        y = y + x.astype(_F32) * D.astype(_F32)[None, :, None]
        pool = pool.at[layer, slots].set(S)
    return y.astype(x.dtype), pool
