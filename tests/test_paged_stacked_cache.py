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

from mxnet_tpu.ops.attention import (PAGED_TILE_TOKENS, paged_attention,
                                     paged_tile_slots)
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


# -- the tile walk (PR 30) --------------------------------------------------------
# Both kernels fold a tile of PAGED_TILE_TOKENS positions a step and walk
# a row's live context only.  The cases sit on the walk's edges: a
# context of nothing, of one token, of one block, one short of a tile, a
# whole tile, one over, the whole table (whose last tile is cut short by
# the table's width), and all of them in one batch.  16-token blocks, so
# that a tile is as many slots as on the chip.

T_BS = 16
T_SLOTS = paged_tile_slots(T_BS)
SPAN = T_SLOTS * T_BS
T_W = 2 * T_SLOTS + T_SLOTS // 2              # two tiles and a half
T_NB = T_W + 8
TABLE = T_W * T_BS
EDGES = {"empty": (0,), "one": (1,), "block": (T_BS,),
         "tile-1": (SPAN - 1,), "tile": (SPAN,), "tile+1": (SPAN + 1,),
         "table": (TABLE,),
         "mixed": (SPAN + 1, 0, T_BS, TABLE, 1, SPAN - 1, SPAN)}
GEOMETRY = {
    # name: (Hq, Hkv, Dh, flat): `flat` is the hybrid's cache, the packed
    # kernel; Hkv 2 is what one of four tensor-parallel shards sees
    "heads": (8, 2, 32, False),
    "heads-hkv8": (16, 8, 16, False),
    "packed": (8, 4, 64, True),
}


def _tile_case(geometry, ctxs, dtype=jnp.float32, seed=0, dead_block=None):
    """(q, K, V, tables, context_lens) over a two-layer stack; layer 0 is
    NaN.  ``dead_block``: the block every slot past a row's context
    names (the null block 0 by default)."""
    Hq, Hkv, Dh, flat = GEOMETRY[geometry]
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(len(ctxs), Hq, Dh).astype(np.float32))
    tail = (Hkv * Dh,) if flat else (Hkv, Dh)
    k = np.full((2, T_NB, T_BS) + tail, np.nan, np.float32)
    v = np.full((2, T_NB, T_BS) + tail, np.nan, np.float32)
    k[1] = rng.randn(*k.shape[1:])
    v[1] = rng.randn(*v.shape[1:])
    bt = np.full((len(ctxs), T_W), dead_block or 0, np.int32)
    for b, c in enumerate(ctxs):
        nblk = -(-c // T_BS)
        bt[b, :nblk] = rng.choice(np.arange(1, T_NB - 1), nblk,
                                  replace=False)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), jnp.asarray(bt),
            jnp.asarray(ctxs, jnp.int32))


def _attend(geometry, impl, q, k, v, bt, ctx, **kw):
    _, Hkv, _, flat = GEOMETRY[geometry]
    if flat:
        kw["flat_heads"] = Hkv
    return paged_attention(q, k, v, bt, ctx, layer=1, impl=impl, **kw)


def _close(out, ref, ctxs, tol=2e-5):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert out.shape == ref.shape and np.isfinite(out).all()
    assert np.abs(out - ref).max() < tol, np.abs(out - ref).max()
    for b, c in enumerate(ctxs):
        if c == 0:
            assert np.abs(out[b]).max() == 0.0


def test_tile_geometry_is_the_chips():
    assert T_SLOTS * T_BS == PAGED_TILE_TOKENS >= 128
    assert T_W % T_SLOTS                    # the table ends inside a tile


@pytest.mark.parametrize("edge", sorted(EDGES))
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_tile_walk_matches_oracle_at_its_edges(geometry, edge):
    """A batch of ONE row for each edge (the smallest decode bucket: no
    second row to start copies for), and every edge in one batch."""
    ctxs = EDGES[edge]
    case = _tile_case(geometry, ctxs)
    _close(_attend(geometry, "pallas", *case),
           _attend(geometry, "jnp", *case), ctxs)


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_nothing_past_the_context_reaches_a_sum(geometry):
    """Every table slot past a row's context names a block full of NaN
    (layer 1's, the layer read): the walk addresses no such slot, so no
    NaN can reach a product, and the outputs are the oracle's with the
    null block there instead."""
    ctxs = EDGES["mixed"]
    q, k, v, bt, ctx = _tile_case(geometry, ctxs, dead_block=T_NB - 1)
    k = k.at[1, T_NB - 1].set(jnp.nan)
    v = v.at[1, T_NB - 1].set(jnp.nan)
    clean = jnp.where(bt == T_NB - 1, 0, bt)
    _close(_attend(geometry, "pallas", q, k, v, bt, ctx),
           _attend(geometry, "jnp", q, k, v, clean, ctx), ctxs)


@pytest.mark.parametrize("window", [SPAN // 2 + 3, SPAN + 5, 2 * SPAN])
def test_window_band_starting_inside_a_tile(window):
    """The band's first position falls inside a tile (the first one, or
    a later one, so tiles below it are never walked), and covers more
    than the context for the short rows."""
    ctxs = (2 * SPAN + 9, SPAN + 1, 7, 0, TABLE)
    case = _tile_case("heads", ctxs, seed=2)
    _close(_attend("heads", "pallas", *case, window=window),
           _attend("heads", "jnp", *case, window=window), ctxs)


def test_int8_scales_ride_the_tile():
    """int8 K/V over the tile's edges: a slot's scales apply to its own
    columns of the scores and probabilities."""
    ctxs = EDGES["mixed"]
    Hq, Hkv, Dh, _ = GEOMETRY["heads"]
    q, k, v, bt, ctx = _tile_case("heads", ctxs, seed=3)
    rng = np.random.RandomState(4)
    ks = rng.rand(2, T_NB, T_BS, Hkv).astype(np.float32) * 0.02 + 0.005
    vs = rng.rand(2, T_NB, T_BS, Hkv).astype(np.float32) * 0.02 + 0.005
    kq = np.clip(np.round(np.nan_to_num(np.asarray(k)) / ks[..., None]),
                 -127, 127).astype(np.int8)
    vq = np.clip(np.round(np.nan_to_num(np.asarray(v)) / vs[..., None]),
                 -127, 127).astype(np.int8)
    ks[0] = vs[0] = np.nan                      # the layer not read
    kw = {"k_scale": jnp.asarray(ks), "v_scale": jnp.asarray(vs)}
    case = (q, jnp.asarray(kq), jnp.asarray(vq), bt, ctx)
    _close(_attend("heads", "pallas", *case, **kw),
           _attend("heads", "jnp", *case, **kw), ctxs)


def test_decode_span_counts_the_tiles_walked():
    """``serve.decode`` says how much of the table a layer's kernel call
    walks (``kv_tiles``, over the live rows' contexts) and what a walk
    of every row's whole table would visit (``kv_tiles_table``)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    S, vocab = 2 * SPAN, 53
    net = mx.models.gpt(vocab, S, num_layers=1, d_model=32, num_heads=4)
    shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(0)
    params = {n: (rng.randn(*s) * 0.1 + n.endswith("gamma")).astype(
        np.float32) for n, s in zip(net.list_arguments(), shapes)
        if n not in ("data", "softmax_label")}
    telemetry.reset()
    telemetry.enable()
    try:
        eng = mx.serve.Engine(params, symbol=net, block_size=T_BS,
                              num_blocks=64, max_batch=4,
                              max_model_len=S, prefill_chunk=0)
        prompt = rng.randint(0, vocab, (SPAN - 1,)).astype(np.int32)
        eng.submit(prompt, max_new_tokens=3)
        eng.run()
        table_tiles = -(-eng.table_width // T_SLOTS)
        eng.shutdown()
        spans = telemetry.tracer().spans(prefix="serve.decode")
    finally:
        telemetry.disable()
        telemetry.reset()
    args = [s[5] for s in spans if s[0] == "serve.decode"]
    # prefill emits the first token; two decode steps follow: the row's
    # context (the token being written included) is SPAN positions, one
    # whole tile, then SPAN + 1, a second tile begun
    assert [(a["batch"], a["bucket"], a["kv_tiles"], a["kv_tiles_table"])
            for a in args] == [(1, 1, 1, table_tiles), (1, 1, 2, table_tiles)]
    assert table_tiles == 2
