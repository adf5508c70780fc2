"""Routed experts for the serving programs, dropless, over the experts a
program HOLDS.

A routed feed-forward layer scores every row against all ``E`` experts,
keeps each row's ``k`` best and adds their weighted outputs.  A serving
program may hold only experts ``[offset, offset + count)`` (one chip's
share of a layer whose experts are spread over several): it routes over
all ``E``, computes its own experts' part and leaves the rest to the
other shares.  Nothing is ever dropped: there is no capacity factor, a
busy expert simply gets more rows.

- :func:`route`: float32 scores over all experts (a softmax, or
  sigmoids picked with a selection bias), the ``k`` largest, their
  weights divided by their sum.
- :func:`dispatch`: the picks of held experts sorted by expert, and the
  rows each held expert got (its *group*); picks of absent experts and of
  a bucket's padding rows sort behind every group and belong to none.
- :func:`grouped_matmul`: ``rows (M, K) x experts (G, K, N)`` where the
  sorted rows of group ``g`` meet expert ``g``'s matrix.  On a TPU the
  Mosaic grouped-matmul kernel that ships with jax (``megablox.gmm``:
  its grid visits only the row tiles that hold rows and reads only the
  experts that got any), elsewhere ``lax.ragged_dot``.
- :func:`routed_experts`: gather, up(-gate) product, the expert's
  activation (SwiGLU, or an ungated squared ReLU), down product,
  weighted sum back onto the rows; all device work under the name scope
  :data:`SCOPE`, whatever implements it.  A program that holds a small
  share of the experts works on the front of the sorted picks where the
  held ones fit it, on all of them otherwise (:func:`short_path`).  Also
  returns the router's counts (:data:`STATS`) for the step record.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_util

__all__ = ["route", "dispatch", "grouped_matmul", "routed_experts",
           "short_path", "resolve_moe_impl", "relu2", "SCOPE", "STATS"]

# named scope of the experts' device work: device-trace operation names
# carry it
SCOPE = "moe_experts"
# what :func:`routed_experts` counts, in this order (int32 each): picks of
# real rows, those of held experts, the busiest held expert's rows, held
# experts with at least one row, and whether the layer ran on the short
# path (summed over a program's routed layers: how many did)
STATS = ("moe_picks", "moe_picks_held", "moe_load_max", "moe_experts_hit",
         "moe_short_layers")

# (rows, K, N) tiles of the Mosaic kernel.  Rows: a decode pass has a row
# or two an expert and a span tens (2048 rows x 10 picks over 256 experts:
# 80), and a tile that visits a group is computed whole, so a tall tile is
# mostly padding (at 512 rows a span's products ran at a sixth of their
# time at 128; my chip run, PR 34).  K, N: an expert's matrix in few, large
# pieces, both passes being bound by reading it: at most this wide, and
# whole lane groups that divide the axis where there are such (:func:`_tile`)
_TILE_M = 128
_TILE_K = 1024
_TILE_N = 1024
# The picks of held experts are sorted in front of all others.  A uniform
# router gives a program that holds ``count`` of ``E`` experts count / E
# of a pass's picks; where SHORT_MARGIN times that many (in whole row
# tiles) hold them, only those rows are gathered and multiplied, and all
# otherwise: nothing is ever dropped.  Both paths are compiled into a
# program only where the short one spares at least SHORT_MIN_SPARED picks
# (a decode pass gathers a few hundred rows either way).
_SHORT_MARGIN = 2
_SHORT_MIN_SPARED = 3072


def resolve_moe_impl(impl=None):
    """``"pallas"`` (the Mosaic grouped matmul) on a TPU, else
    ``"ragged"`` (``lax.ragged_dot``)."""
    if impl is None:
        impl = "pallas" if pallas_util.on_tpu() else "ragged"
    if impl not in ("pallas", "ragged"):
        raise ValueError(f"moe: impl must be pallas|ragged (got {impl!r})")
    return impl


def route(logits, top_k, score="softmax", bias=None):
    """``logits (T, E)`` -> ``(idx (T, k) int32, w (T, k) float32)``: each
    row's ``k`` best experts over ALL experts and their scores divided by
    their sum, everything float32.  ``score="softmax"``: the ``k`` most
    probable under a softmax.  ``score="sigmoid"``: each expert's score
    is the sigmoid of its logit, and the ``k`` largest of ``score +
    bias`` are picked (``bias (E,)``, the selection bias: it moves
    picks, never a weight)."""
    if score == "softmax":
        pr = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        w, idx = jax.lax.top_k(pr, top_k)
    elif score == "sigmoid":
        pr = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, idx = jax.lax.top_k(
            pr if bias is None else pr + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(pr, idx, axis=-1)
    else:
        raise ValueError(f"moe: score must be softmax|sigmoid "
                         f"(got {score!r})")
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def dispatch(idx, offset, count, valid=None):
    """Sort the picks ``idx (T, k)`` by held expert.

    Returns ``(order, sizes, held)``: ``order (T * k,)`` the flat picks
    (``row * k + j``) with those of held experts first, grouped by
    expert; ``sizes (count,)`` int32 the rows of each held expert;
    ``held (T, k)`` which picks are of held experts (and of real rows:
    ``valid (T,)`` marks them, None = all)."""
    local = idx - jnp.int32(offset)
    held = jnp.logical_and(local >= 0, local < count)
    if valid is not None:
        held = jnp.logical_and(held, valid[:, None])
    key = jnp.where(held, local, jnp.int32(count)).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    return order, sizes, held


def _tile(n, cap):
    """The widest tile of ``n`` columns: ``n`` itself under ``cap``, else
    the largest multiple of 128 up to ``cap`` that divides ``n`` (2688 =
    3 x 896: no ragged last tile), else ``cap``."""
    if n <= cap:
        return n
    return next((t for t in range(cap - cap % 128, 0, -128) if n % t == 0),
                cap)


def grouped_matmul(x, w, sizes, impl=None, interpret=None):
    """``x (M, K)`` sorted by group, ``w (G, K, N)``, ``sizes (G,)``: row
    ``r`` of group ``g`` times ``w[g]``.  Rows past ``sum(sizes)`` belong
    to no group; what they return is unspecified (finite or not) under
    the kernel, zero under ``ragged_dot``.  Returns ``(M, N)`` in x's
    dtype, accumulated in float32."""
    if resolve_moe_impl(impl) == "ragged":
        return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    M, K = x.shape
    N = w.shape[-1]
    tm = _TILE_M
    pad = -M % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    if interpret is None:
        interpret = not pallas_util.on_tpu()
    # the package runs under jax_enable_x64 and the kernel's grid bound is
    # a sum, an int64 there, which the chip's compiler does not take
    with jax.enable_x64(False):
        out = gmm(x, w.astype(x.dtype), sizes,
                  preferred_element_type=x.dtype,
                  tiling=(tm, _tile(K, _TILE_K), _tile(N, _TILE_N)),
                  interpret=bool(interpret))
    return out[:M] if pad else out


def short_path(picks, count, num_experts):
    """How many of a pass's ``picks`` sorted picks the short path works
    on, or None where a program has the one path over all of them: it
    holds so many of the ``num_experts`` experts that the margin covers
    every pick (a whole model's program always), or the pass is small."""
    tiles = -(-picks * _SHORT_MARGIN * count // (num_experts * _TILE_M))
    short = tiles * _TILE_M
    return short if picks - short >= _SHORT_MIN_SPARED else None


def _silu_f32(x):
    xf = x.astype(jnp.float32)
    return xf * jax.nn.sigmoid(xf)


def relu2(x):
    """``relu(x)^2``, squared in float32, in x's dtype."""
    r = jnp.maximum(x.astype(jnp.float32), np.float32(0.0))
    return (r * r).astype(x.dtype)


# jitted so that the routed layers of a program share ONE trace
@functools.partial(jax.jit, static_argnames=("offset", "num_experts", "impl",
                                             "act"))
def routed_experts(x, w_in, w_out, idx, w, offset, num_experts, valid=None,
                   impl=None, act="swiglu"):
    """The held experts' part of a routed layer over rows ``x (T, D)``.

    ``w_in (count, D, 2 F)`` (gate columns first) and ``w_out (count, F,
    D)`` are the held experts' SwiGLU matrices (``act="swiglu"``), or
    ``w_in (count, D, F)`` those of ungated experts ``W_out relu(W_in
    x)^2`` (``act="relu2"``); ``D`` is whatever width the experts work
    in (the model's, or a latent).  ``idx``/``w (T, k)`` are what
    :func:`route` returned, ``offset`` the first held expert's id,
    ``num_experts`` how many the router scores.
    Returns ``(y (T, D) float32, stats int32)``: ``y[t] = sum over
    row t's picks of held experts of w * E(x[t])`` (zero for a row with
    none, and for rows ``valid`` marks as padding), and :data:`STATS`."""
    if act not in ("swiglu", "relu2"):
        raise ValueError(f"moe: act must be swiglu|relu2 (got {act!r})")
    T, k = idx.shape
    count, D, F2 = w_in.shape
    with jax.named_scope(SCOPE):
        order, sizes, held = dispatch(idx, offset, count, valid)
        # where each pick sits among the sorted ones
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32)).reshape(T, k)

        def over(m):
            """The held experts over the first ``m`` sorted picks, summed
            back onto the rows."""
            xs = x[order[:m] // k]                             # (m, D)
            gu = grouped_matmul(xs, w_in, sizes, impl)
            if act == "swiglu":
                hid = (_silu_f32(gu[:, :F2 // 2]).astype(gu.dtype)
                       * gu[:, F2 // 2:])
            else:
                hid = relu2(gu)
            ys = grouped_matmul(hid, w_out, sizes, impl)       # (m, D)
            y = jnp.zeros((T, D), jnp.float32)
            for j in range(k):
                # a pick of no group reads garbage and multiplies it by
                # nothing: select, never 0 * garbage
                row = ys[jnp.minimum(back[:, j], m - 1)].astype(jnp.float32)
                y = y + jnp.where(held[:, j:j + 1], w[:, j:j + 1] * row,
                                  np.float32(0.0))
            return y

        M = T * k
        short = short_path(M, count, num_experts)
        if short is None:
            y, fits = over(M), jnp.bool_(False)
        else:
            fits = jnp.sum(sizes) <= short
            y = jax.lax.cond(fits, lambda: over(short), lambda: over(M))
    real = (jnp.int32(T) if valid is None
            else jnp.sum(valid, dtype=jnp.int32))
    stats = jnp.stack([real * jnp.int32(k), jnp.sum(sizes), jnp.max(sizes),
                       jnp.sum(sizes > 0, dtype=jnp.int32),
                       fits.astype(jnp.int32)])
    return y, stats
