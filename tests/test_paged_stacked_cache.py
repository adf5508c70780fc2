"""The paged-attention op reads a layer of the STACKED cache in place.

``serve.Engine`` keeps one ``(L, num_blocks, block_size, Hkv, Dh)`` array
per K and V.  Its programs hand that stack to ``paged_attention`` whole,
with a static ``layer``: the Mosaic kernel addresses the layer in its
DMA index and the jnp formulation in its one gather, so no layer is ever
sliced out (on the chip such a slice in front of a custom call was a
copy of the layer's whole pool, 32 times a decode step: PERF.md, PR 27).

Every case here reads one layer of a three-layer stack whose OTHER
layers hold NaN (int8: saturated values under NaN scales), so a wrong
layer index cannot pass, and compares with the jnp oracle on that
layer's own 4-D cache.  Rows: one that ends mid-block, one empty, one
that ends mid-block further down the table, one that ends on a block's
edge.  The kernel runs through the Pallas interpreter, as everywhere
off the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.attention import paged_attention
from mxnet_tpu.ops.pallas_paged_attention import paged_attention_kernel

L, NB, BS, HQ, HKV, DH, W = 3, 16, 4, 8, 2, 32, 6
CTX = (9, 0, 21, 8)
VARIANTS = {
    # name: (cache dtype, window, tolerance against the oracle)
    "bf16": (jnp.bfloat16, 0, 3e-2),
    "int8kv": (jnp.int8, 0, 2e-6),
    "windowed": (jnp.float32, 5, 2e-6),
}


def _stacked_case(variant, layer, seed=0):
    """(q, stacked K, stacked V, tables, context_lens, scales or None,
    the layer's own 4-D K, V and scales) with garbage in every other
    layer."""
    dtype, _, _ = VARIANTS[variant]
    rng = np.random.RandomState(seed)
    quant = dtype == jnp.int8
    qdt = jnp.float32 if quant else dtype
    q = jnp.asarray(rng.randn(len(CTX), HQ, DH).astype(np.float32), qdt)
    k1 = rng.randn(NB, BS, HKV, DH).astype(np.float32)
    v1 = rng.randn(NB, BS, HKV, DH).astype(np.float32)
    bt = np.zeros((len(CTX), W), np.int32)
    for b, c in enumerate(CTX):
        nblk = -(-c // BS)
        bt[b, :nblk] = rng.choice(np.arange(1, NB), nblk, replace=False)
    scales = one_scales = None
    if quant:
        ks1 = rng.rand(NB, BS, HKV).astype(np.float32) * 0.02 + 0.005
        vs1 = rng.rand(NB, BS, HKV).astype(np.float32) * 0.02 + 0.005
        k1 = np.clip(np.round(k1 / ks1[..., None]), -127, 127)
        v1 = np.clip(np.round(v1 / vs1[..., None]), -127, 127)
        bad, bad_scale = 127, np.nan
        ks = np.full((L,) + ks1.shape, bad_scale, np.float32)
        vs = np.full((L,) + vs1.shape, bad_scale, np.float32)
        ks[layer], vs[layer] = ks1, vs1
        scales = (jnp.asarray(ks), jnp.asarray(vs))
        one_scales = (jnp.asarray(ks1), jnp.asarray(vs1))
    else:
        bad = np.nan
    k = np.full((L,) + k1.shape, bad, np.float32)
    v = np.full((L,) + v1.shape, bad, np.float32)
    k[layer], v[layer] = k1, v1
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(bt), jnp.asarray(CTX, jnp.int32), scales,
            jnp.asarray(k1, dtype), jnp.asarray(v1, dtype), one_scales)


def _scale_kw(scales):
    return {} if scales is None else {"k_scale": scales[0],
                                      "v_scale": scales[1]}


def _check(out, ref, tol):
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() < tol
    assert np.abs(out[CTX.index(0)]).max() == 0.0      # the empty row


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("layer", [0, 1, L - 1])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_stacked_cache_layer_matches_oracle(variant, layer, impl):
    _, window, tol = VARIANTS[variant]
    q, k, v, bt, ctx, scales, k1, v1, one_scales = _stacked_case(
        variant, layer)
    ref = paged_attention(q, k1, v1, bt, ctx, window=window, impl="jnp",
                          **_scale_kw(one_scales))
    out = paged_attention(q, k, v, bt, ctx, window=window, impl=impl,
                          layer=layer, **_scale_kw(scales))
    assert out.shape == ref.shape and out.dtype == ref.dtype
    _check(out, ref, tol)


@pytest.mark.parametrize("variant", ["bf16", "int8kv"])
def test_stacked_cache_kernel_under_head_sharded_mesh(variant):
    """Two devices, the cache's head axis split over them: the kernel
    runs per head shard inside ``shard_map`` on the 5-D stack (and the
    4-D scales' stack), as ``Engine(tp=2)`` runs it."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    _, window, tol = VARIANTS[variant]
    layer = 1
    q, k, v, bt, ctx, scales, k1, v1, one_scales = _stacked_case(
        variant, layer, seed=1)
    ref = paged_attention(q, k1, v1, bt, ctx, window=window, impl="jnp",
                          **_scale_kw(one_scales))
    cache_sh = NamedSharding(mesh, P(None, None, None, "tp", None))
    k, v = jax.device_put(k, cache_sh), jax.device_put(v, cache_sh)
    if scales is not None:
        scale_sh = NamedSharding(mesh, P(None, None, None, "tp"))
        scales = tuple(jax.device_put(s, scale_sh) for s in scales)

    @jax.jit
    def run(q, k, v, bt, ctx, *scales):
        return paged_attention(q, k, v, bt, ctx, window=window,
                               impl="pallas", layer=layer, mesh=mesh,
                               head_axis="tp", **_scale_kw(scales or None))

    _check(run(q, k, v, bt, ctx, *(scales or ())), ref, tol)


def test_single_layer_cache_is_the_stacked_kernel_at_layer_zero():
    """A lone 4-D cache goes through the same kernel as ``cache[None]``,
    ``layer=0``: bit-identical outputs, no second path."""
    q, k, v, bt, ctx, _, k1, v1, _ = _stacked_case("windowed", 0)
    alone = paged_attention_kernel(q, k1, v1, bt, ctx, window=5)
    stacked = paged_attention_kernel(q, k1[None], v1[None], bt, ctx,
                                     window=5, layer=0)
    assert np.array_equal(np.asarray(alone), np.asarray(stacked))


def test_layer_argument_is_validated():
    q, k, v, bt, ctx, _, k1, v1, _ = _stacked_case("windowed", 0)
    for impl in ("jnp", "pallas"):
        with pytest.raises(ValueError, match="layer"):
            paged_attention(q, k, v, bt, ctx, impl=impl)        # no layer
        with pytest.raises(ValueError, match="layer"):
            paged_attention(q, k1, v1, bt, ctx, impl=impl, layer=0)
    with pytest.raises(ValueError, match="layer"):
        paged_attention_kernel(q, k, v, bt, ctx, layer=L)       # past end
    with pytest.raises(ValueError, match="layer"):
        paged_attention_kernel(q, k, v, bt, ctx)
