"""Chip-free performance contract.

Three layers of CPU-only gates that catch perf regressions the moment
they are introduced, instead of on hardware:

1. **Kernel lowerability**: every Pallas kernel must pass Mosaic (TPU)
   lowering via cross-platform AOT (``.lower(lowering_platforms=
   ("tpu",))`` works without a chip — Mosaic lowers at lowering time).
   An earlier round found the flash kernel failed this at EVERY shape
   (weak-f64 constants + an lse BlockSpec violating Mosaic tiling): the
   GPT bench would have crashed on first contact with a chip.  These
   tests make that class of bug a CI failure — and hold the serve
   programs to the same bar (no f64 tensors, the paged kernel compiled
   in, tp > 1 lowerable).

2. **HLO structural audits** (tools/hlo_audit.py): the lowered bench
   train steps must keep their layout properties — ResNet-50/CIFAR with
   zero activation transposes, sequence-major GPT with none beyond the
   tiny D-free lse row maps.

3. **Collective-shape audits**: the compiled dp x tp sharded step and
   the ring/Ulysses attention programs must contain exactly the
   collective families their designs call for (reference analog: the
   comm patterns ps-lite/NCCL hard-coded; here XLA inserts them and
   these tests pin what it inserted).

Plus the artifact regression gate (tools/compare_baseline.py --check).
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import hlo_audit  # noqa: E402  (repo tool, imported for its builders)


@pytest.fixture(autouse=True)
def _default_trace_env(monkeypatch):
    """The audits pin properties of the DEFAULT bench program; shield
    them from env leaked by earlier in-process tests (found in round 4:
    examples/memcost.py left MXNET_BACKWARD_DO_MIRROR=1 behind, adding
    remat to every later trace and shifting the audited op counts)."""
    monkeypatch.delenv("MXNET_BACKWARD_DO_MIRROR", raising=False)


def _tpu_text(fn, *args):
    """StableHLO of ``fn`` lowered FOR TPU from the CPU backend."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _counts(text):
    return hlo_audit.audit_counts(text)


# -- 1. Pallas kernels must lower for TPU -----------------------------------

@pytest.mark.parametrize("layout,shape", [
    ("bhsd", (2, 8, 1024, 64)),     # bench_gpt-class shape
    ("bshd", (2, 1024, 8, 64)),     # sequence-major variant
    ("bhsd", (1, 1, 128, 128)),     # the _flash_available probe shape
    ("bshd", (1, 128, 1, 128)),
])
def test_flash_kernel_lowers_for_tpu(layout, shape):
    from mxnet_tpu.ops.flash_attention import flash_attention

    def fwd(q):
        return flash_attention(q, q, q, causal=True, interpret=False,
                               layout=layout)

    def bwd(q):
        return jax.grad(lambda x: flash_attention(
            x, x, x, causal=True, interpret=False,
            layout=layout).astype(jnp.float32).sum())(q)

    q = jnp.zeros(shape, jnp.bfloat16)
    t = _tpu_text(fwd, q)
    assert len(re.findall(r"tpu_custom_call", t)) == 1, \
        "forward did not lower to one Mosaic kernel"
    t = _tpu_text(bwd, q)
    # fwd (rerun in vjp) + dq kernel + dkv kernel
    assert len(re.findall(r"tpu_custom_call", t)) == 3, \
        "backward did not lower to three Mosaic kernels"


@pytest.mark.parametrize("opts,qshape,kshape", [
    # sliding window: band-masked tiles + tile skipping
    ({"window": 256}, (2, 8, 1024, 64), None),
    # GQA (bshd native): 8 q heads on 2 kv heads
    ({"layout": "bshd"}, (2, 1024, 8, 64), (2, 1024, 2, 64)),
    # GQA + window + causal composed
    ({"layout": "bshd", "window": 256}, (2, 1024, 8, 64), (2, 1024, 2, 64)),
])
def test_flash_kernel_features_lower_for_tpu(opts, qshape, kshape):
    """The window/GQA kernel variants must survive Mosaic lowering, not
    just the CPU interpreter — the x64-index-map bug class hid exactly
    here (pallas_util.idx32)."""
    from mxnet_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros(qshape, jnp.bfloat16)
    k = q if kshape is None else jnp.zeros(kshape, jnp.bfloat16)

    def fwd(q, k):
        return flash_attention(q, k, k, causal=True, interpret=False,
                               **opts)

    def bwd(q, k):
        # differentiate BOTH operands: k/v grads unused would let XLA
        # DCE the dkv kernel and the count would vacuously pass at 2
        return jax.grad(lambda x, y: flash_attention(
            x, y, y, causal=True, interpret=False,
            **opts).astype(jnp.float32).sum(), argnums=(0, 1))(q, k)

    t = _tpu_text(fwd, q, k)
    assert len(re.findall(r"tpu_custom_call", t)) == 1
    t = _tpu_text(bwd, q, k)
    assert len(re.findall(r"tpu_custom_call", t)) == 3


def test_fused_rnn_kernels_lower_for_tpu():
    from mxnet_tpu.ops.pallas_gru import fused_gru
    from mxnet_tpu.ops.pallas_lstm import fused_lstm

    T, N, H = 128, 32, 512          # FLASH_BENCH/RNN-bench shape class
    h0 = jnp.zeros((N, H), jnp.float32)

    gx = jnp.zeros((T, N, 4 * H), jnp.float32)
    c0 = jnp.zeros((N, H), jnp.float32)
    wh = jnp.zeros((4 * H, H), jnp.float32)
    bh = jnp.zeros((4 * H,), jnp.float32)
    t = _tpu_text(lambda a: fused_lstm(a, h0, c0, wh, bh,
                                       interpret=False)[0], gx)
    assert "tpu_custom_call" in t
    t = _tpu_text(lambda a: jax.grad(lambda x: fused_lstm(
        x, h0, c0, wh, bh, interpret=False)[0].sum())(a), gx)
    assert len(re.findall(r"tpu_custom_call", t)) >= 2   # fwd + bwd kernels

    gxg = jnp.zeros((T, N, 3 * H), jnp.float32)
    whg = jnp.zeros((3 * H, H), jnp.float32)
    bhg = jnp.zeros((3 * H,), jnp.float32)
    t = _tpu_text(lambda a: fused_gru(a, h0, whg, bhg,
                                      interpret=False)[0], gxg)
    assert "tpu_custom_call" in t
    t = _tpu_text(lambda a: jax.grad(lambda x: fused_gru(
        x, h0, whg, bhg, interpret=False)[0].sum())(a), gxg)
    assert len(re.findall(r"tpu_custom_call", t)) >= 2


def _nonscalar_f64(text):
    return re.findall(r"tensor<[0-9x]+xf64>", text)


@pytest.mark.parametrize("cache", ["stacked", "single"])
@pytest.mark.parametrize("variant", ["bf16", "int8kv", "windowed"])
def test_paged_kernel_lowers_for_tpu(variant, cache):
    """The paged-decode kernel is the TPU default of the serve engine
    and must pass Mosaic lowering compiled (``interpret=False``) at a
    served geometry: 8 rows, 32 q / 8 kv heads of 128, 16-token blocks.
    ``stacked`` is the engine's call: the whole ``(L, ...)`` cache and a
    static layer, which must reach the custom call whole, with no slice
    in front of it: seen as ``(L, num_blocks, block_size * Hkv, Dh)``,
    each block the matrix it already is in memory (the scales a row of
    ``block_size * Hkv``).  ``single`` is a lone 4-D cache, the same
    kernel through ``cache[None]``."""
    from mxnet_tpu.ops.pallas_paged_attention import paged_attention_kernel

    B, Hq, Hkv, Dh, bs, W, nb, L = 8, 32, 8, 128, 16, 32, 65, 4
    quant = variant == "int8kv"
    lead, layer = ((L,), 2) if cache == "stacked" else ((), None)
    q = jnp.zeros((B, Hq, Dh), jnp.bfloat16)
    kc = jnp.zeros(lead + (nb, bs, Hkv, Dh),
                   jnp.int8 if quant else jnp.bfloat16)
    sc = jnp.ones(lead + (nb, bs, Hkv), jnp.float32) if quant else None
    bt = jnp.zeros((B, W), jnp.int32)
    ctx = jnp.ones((B,), jnp.int32)

    def fwd(q, kc, sc, bt, ctx):
        return paged_attention_kernel(
            q, kc, kc, bt, ctx, window=64 if variant == "windowed" else 0,
            k_scale=sc, v_scale=sc, interpret=False, layer=layer)

    t = _tpu_text(fwd, q, kc, sc, bt, ctx)
    assert len(re.findall(r"tpu_custom_call", t)) == 1
    assert not _nonscalar_f64(t)
    assert not re.search(r"stablehlo\.(dynamic_)?slice\b", t)
    (operands,) = hlo_audit.custom_call_operand_dims(t)
    n_layers = L if cache == "stacked" else 1
    assert operands.count((n_layers, nb, bs * Hkv, Dh)) == 2, operands
    assert operands.count((n_layers, nb, 1, bs * Hkv)) == (2 if quant else 0)


@pytest.mark.parametrize("tp", [1, 2])
def test_serve_programs_lower_for_tpu(tp):
    """The serve programs as a TPU sees them, lowered from the CPU with
    bf16 parameters: no program scores attention (or anything else) in a
    non-scalar f64 — ``jax_enable_x64`` is on package-wide and a NumPy
    float64 scalar is not weak-typed — and decode runs the compiled
    paged kernel.  At tp=2 the kernel sits inside a GSPMD jit with a
    head-sharded cache: it must be shard_map'd, or lowering raises
    "Mosaic kernels cannot be automatically partitioned"."""
    with hlo_audit.assume_tpu():
        eng = hlo_audit.build_serve_engine(dtype="bfloat16", tp=tp,
                                           block_size=8)
        try:
            assert eng.statusz()["paged_attention"] == "pallas"
            for kind, bucket in (("prefill", 8), ("chunk", 8),
                                 ("decode", 4), ("verify", 4)):
                t = hlo_audit.serve_lower_text(eng, kind, bucket,
                                               platform="tpu")
                assert not _nonscalar_f64(t), (kind, _nonscalar_f64(t)[:3])
                if kind == "decode":
                    # one kernel per layer
                    assert len(re.findall(r"tpu_custom_call", t)) == 2
        finally:
            eng.shutdown()


@pytest.fixture(scope="module", params=[1, 2], ids=["tp1", "tp2"])
def serve_tpu_texts(request):
    """TPU-lowered StableHLO of the cache-READING serve programs at
    ``tp`` 1 and 2 (bf16 parameters, the paged kernel on), and the
    stacked cache's shape.  Lowered once per ``tp``; ``assume_tpu`` is
    left again before any test runs."""
    with hlo_audit.assume_tpu():
        eng = hlo_audit.build_serve_engine(dtype="bfloat16",
                                           tp=request.param, block_size=8)
        try:
            texts = {kind: hlo_audit.serve_lower_text(eng, kind, bucket,
                                                      platform="tpu")
                     for kind, bucket in (("decode", 4), ("chunk", 8),
                                          ("verify", 4), ("draft", 4))}
            return request.param, tuple(eng._cache_k.shape), texts
        finally:
            eng.shutdown()


@pytest.mark.parametrize("kind", ["decode", "chunk", "verify", "draft"])
def test_serve_programs_read_cache_in_place(serve_tpu_texts, kind):
    """A layer of the stacked KV cache is addressed, never sliced: no
    ``slice`` / ``dynamic_slice`` of a serve program yields a whole
    layer of the cache (on the chip each was a copy of the layer's
    pool, K and V, every layer of every pass), and every paged-kernel
    custom call takes the whole stack, a head shard of it at tp=2, each
    block seen as its ``(block_size * Hkv, Dh)`` matrix.  The draft
    program reads its own one-layer stack."""
    tp, cache_shape, texts = serve_tpu_texts
    text = texts[kind]
    assert hlo_audit.cache_layer_slices(text, cache_shape) == []
    calls = hlo_audit.custom_call_operand_dims(text)
    L, nb, bs, Hkv, Dh = cache_shape
    if kind == "decode":
        assert len(calls) == L
        for operands in calls:
            assert operands.count((L, nb, bs * Hkv // tp, Dh)) == 2, operands
    elif kind == "draft":
        # k drafting steps of a one-layer draft model (the write-only
        # last step's attention is dead code); the draft's cache is
        # replicated under tp, so its kernel sees every head
        assert len(calls) == 2
        for operands in calls:
            stacks = [d for d in operands if len(d) == 4]
            assert len(stacks) == 2 and stacks[0] == stacks[1], operands
            assert stacks[0][:2] == (1, nb) and not stacks[0][2] % bs, operands
    else:
        # chunk and verify score through one gather over the stack
        assert calls == []
        gathers = [ln for ln in text.splitlines()
                   if "stablehlo.gather" in ln
                   and f"tensor<{L}x{nb}x{bs}x" in ln]
        assert len(gathers) == 2 * L, (kind, len(gathers))


# -- 1b. The paged kernels and the decode programs, COMPILED for the chip -----
# Lowering (above) ends where Mosaic's own compiler begins: a kernel that
# lowers can still be refused for a tiling, and a program whose kernel
# lowers can still copy its cache pool in front of it.  The chip's
# compiler is installed here and compiles for a chip that is described,
# not attached; these cases run it, at the benchmark cells' attention
# geometry with the depth and the MLP cut (what they pin is per layer).

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _chip_compile(fn, specs, one_chip):
    """``fn`` compiled for the described chip; the persistent cache is
    off around it (an entry written for a described device cannot be
    read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    specs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), specs)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


PAGED_KERNEL_CASES = {
    # name: (rows, Hq, Hkv, Dh, cache dtype, window, flat)
    "doc-batch": (16, 32, 8, 128, jnp.bfloat16, 0, False),
    "chat-concurrent": (64, 32, 8, 64, jnp.bfloat16, 0, True),
    "int8kv": (16, 32, 8, 128, jnp.int8, 0, False),
    "windowed": (16, 32, 8, 128, jnp.bfloat16, 1000, False),
    "tp4-shard": (16, 8, 2, 128, jnp.bfloat16, 0, False),
    "one-row": (1, 32, 8, 128, jnp.bfloat16, 0, False),
    # two kv heads of 128 side by side under 32 query heads (PR 36)
    "reason-long": (64, 32, 2, 128, jnp.bfloat16, 0, True),
}


@pytest.mark.parametrize("case", sorted(PAGED_KERNEL_CASES))
def test_paged_kernels_compile_for_the_chip(case, one_chip):
    """Both paged kernels through the chip's compiler at the cells' real
    shapes (16 rows x 32 heads x 128 and 64 rows x 32 heads x 64 flat,
    256 table slots of 16 tokens) and at what else the engine asks of
    them: int8 K/V, a sliding window, a tensor-parallel shard's two kv
    heads, a bucket of one row.  No temporary of a pool layer's size:
    the views the kernels take of the cache are bitcasts."""
    from mxnet_tpu.ops.attention import paged_attention

    B, Hq, Hkv, Dh, dtype, window, flat = PAGED_KERNEL_CASES[case]
    L, nb, bs, W = 4, 513, 16, 256
    quant = dtype == jnp.int8
    S = jax.ShapeDtypeStruct
    cache = S((L, nb, bs) + ((Hkv * Dh,) if flat else (Hkv, Dh)), dtype)
    scales = [S((L, nb, bs, Hkv), jnp.float32)] * 2 if quant else []
    kw = {"flat_heads": Hkv} if flat else {"window": window}

    def fwd(q, kc, vc, bt, ctx, *sc):
        if sc:
            kw.update(k_scale=sc[0], v_scale=sc[1])
        return paged_attention(q, kc, vc, bt, ctx, layer=2, impl="pallas",
                               **kw)

    with hlo_audit.assume_tpu():
        compiled = _chip_compile(
            jax.jit(fwd), [S((B, Hq, Dh), jnp.bfloat16), cache, cache,
                           S((B, W), jnp.int32), S((B,), jnp.int32)] + scales,
            one_chip)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    layer_bytes = nb * bs * Hkv * Dh * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def _cell_width_engine(kind):
    """An engine with a benchmark cell's attention geometry, batch and
    table (``mistral7b-l16``: 32 / 8 heads x 128, 16 rows; ``granite-
    4.0-h-micro``: 32 / 8 heads x 64 flat, 64 rows; 256 slots x 16
    tokens; the cells' pools, which no faster memory holds), two layers,
    a small MLP and vocabulary."""
    geo = dict(block_size=16, max_model_len=4096, prefill_chunk=512,
               num_blocks=8193 if kind == "hybrid" else 5001)
    if kind == "hybrid":
        dec = mx.models.hybrid_decoder(
            256, 2048, ["mamba", "attention"], num_heads=32, kv_heads=8,
            d_ff=256, mamba_heads=64, mamba_head_dim=64, mamba_state=128)
        return mx.serve.Engine(dec.init_params(0, dtype="bfloat16"),
                               symbol=dec, max_batch=64, **geo), 64
    net = mx.models.gpt(256, 4096, num_layers=2, d_model=4096, num_heads=32,
                        d_ff=256, norm="rmsnorm", mlp="swiglu",
                        pos_embed="rope", tie_embeddings=False, kv_heads=8)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    params = {n: jnp.zeros(s, jnp.bfloat16)
              for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    return mx.serve.Engine(params, symbol=net, max_batch=16, **geo), 16


@pytest.mark.parametrize("kind", ["gpt", "hybrid"])
def test_decode_program_holds_no_copy_of_a_cache_pool(kind, one_chip):
    """The decode program as the chip's compiler leaves it: one Mosaic
    call a layer (the paged kernel; in a hybrid the state update for a
    state-space layer), and the only results of the K/V pool's size are
    the in-place writes, two scatter fusions an attention layer.  A
    ``copy`` of the pool, or a temporary of its size, is what PR 27
    took out; the kernels' views of the stack must stay bitcasts."""
    with hlo_audit.assume_tpu():
        eng, bucket = _cell_width_engine(kind)
        try:
            assert eng.statusz()["paged_attention"] == "pallas"
            # a CPU-built engine does not donate, and a program that
            # does not donate must copy its pool to return it
            eng._donate = True
            compiled = _chip_compile(eng._program_builder("decode", bucket),
                                     eng._program_specs("decode", bucket),
                                     one_chip)
            pool_shape, dtype = eng._cache_k.shape, eng._cache_k.dtype
            n_layers = eng.spec["n_layers"]
        finally:
            eng.shutdown()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_layers
    writes = hlo_audit.pool_sized_results(text, pool_shape)
    assert len(writes) == 2 * pool_shape[0], writes
    assert {op for _, op in writes} == {"fusion"}, writes
    pool_bytes = int(np.prod(pool_shape)) * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


SPAN_KERNEL_CASES = {
    # name: (rows, keys, Hq, Hkv, window)
    "doc-batch-chunk": (2048, 4096, 32, 8, 0),
    "doc-batch-prompt": (2048, 2048, 32, 8, 0),
    "smallest-bucket": (512, 4096, 32, 8, 0),
    "windowed": (2048, 4096, 32, 8, 1000),
    "tp4-shard": (2048, 4096, 8, 2, 0),
    # a view longer than the resident keys: two grid steps a query tile
    "long-view": (2048, 8192, 32, 8, 0),
    # 16 query heads a kv head (PR 36)
    "gqa-16": (2048, 2048, 32, 2, 0),
}


@pytest.mark.parametrize("case", sorted(SPAN_KERNEL_CASES))
def test_span_kernel_compiles_for_the_chip(case, one_chip):
    """The span kernel through the chip's compiler at the cell's real
    shapes and at what else the engine asks of it; through the ONE entry
    point, so the branch the shapes choose is the kernel."""
    from mxnet_tpu.ops.attention import masked_attention, score_scale

    T, S_, Hq, Hkv, window = SPAN_KERNEL_CASES[case]
    S = jax.ShapeDtypeStruct

    def fwd(q, k, v, start, n_valid):
        return masked_attention(q, k, v, start, score_scale(128),
                                window=window, n_valid=n_valid)

    with hlo_audit.assume_tpu():
        compiled = _chip_compile(
            jax.jit(fwd), [S((T, Hq, 128), jnp.bfloat16),
                           S((S_, Hkv, 128), jnp.bfloat16),
                           S((S_, Hkv, 128), jnp.bfloat16),
                           S((), jnp.int32), S((), jnp.int32)], one_chip)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < T * S_


@pytest.mark.parametrize("H,G,rows", [(64, 1, 64), (128, 8, 64),
                                      (128, 8, 8)])
def test_state_update_kernel_compiles_for_the_chip(H, G, rows, one_chip):
    """The single-token state update through the chip's compiler at the
    two hybrid cells' shapes: 64 heads in one state group, and 128 heads
    in 8 groups (a grid step takes 64 heads = 4 whole groups with their
    own B and C rows).  The pool is updated in place: no temporary of a
    layer's pool."""
    from mxnet_tpu.ops.ssm import ssm_state_update

    S = jax.ShapeDtypeStruct
    L, slots, P, N = 5, 65, 64, 128
    bc = (rows, N) if G == 1 else (rows, G, N)

    def fwd(pool, sl, x, dt, dA, Bm, Cm, D):
        return ssm_state_update(pool, 3, sl, x, dt, dA, Bm, Cm, D)

    with hlo_audit.assume_tpu():
        compiled = _chip_compile(
            jax.jit(fwd, donate_argnums=0),
            [S((L, slots, H, P, N), jnp.float32), S((rows,), jnp.int32),
             S((rows, H, P), jnp.bfloat16), S((rows, H), jnp.float32),
             S((rows, H), jnp.float32), S(bc, jnp.bfloat16),
             S(bc, jnp.bfloat16), S((H,), jnp.float32)], one_chip)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    layer_bytes = slots * H * P * N * 4
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 4


@pytest.mark.parametrize("act,K,F,D", [("swiglu", 3072, 1024, 3072),
                                       ("relu2", 1024, 2688, 1024)])
def test_routed_experts_compile_for_the_chip(act, K, F, D, one_chip):
    """The held experts' two grouped products through the chip's compiler
    at the two routed cells' widths: SwiGLU experts in the model width,
    and ungated experts in a 1024-wide latent whose hidden width 2688 no
    1024-column tile divides (the tiles are 896 there)."""
    from mxnet_tpu.ops import moe as moe_ops

    S = jax.ShapeDtypeStruct
    T, k, count, E = 64, 22, 128, 512
    gated = 2 if act == "swiglu" else 1

    def fwd(x, w_in, w_out, idx, w):
        return moe_ops.routed_experts(x, w_in, w_out, idx, w, 0, E, act=act)

    with hlo_audit.assume_tpu():
        compiled = _chip_compile(
            jax.jit(fwd),
            [S((T, K), jnp.bfloat16), S((count, K, gated * F), jnp.bfloat16),
             S((count, F, D), jnp.bfloat16), S((T, k), jnp.int32),
             S((T, k), jnp.float32)], one_chip)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("kind,bucket,keys", [("chunk", 2048, 4096),
                                              ("prefill", 2048, 2048)])
def test_span_programs_hold_no_score_tensor(kind, bucket, keys, one_chip):
    """The chunk program of 2048 rows over the 256-slot table and the
    2048-row prefill program at doc-batch's width, as the chip's compiler
    leaves them: one Mosaic call a layer (the span kernel), no f32 result
    as large as ONE kv head's scores, ``rows x keys`` for its 4 query
    heads (the dense form wrote all 8 together, 1.07 GB a layer in the
    chunk program, three times over), and temporaries under that one
    tensor."""
    with hlo_audit.assume_tpu():
        eng, _ = _cell_width_engine("gpt")
        try:
            assert eng.statusz()["span_attention"] == "kernel"
            assert eng._span_impl(bucket, keys) == "kernel"
            eng._donate = True
            compiled = _chip_compile(eng._program_builder(kind, bucket),
                                     eng._program_specs(kind, bucket),
                                     one_chip)
            n_layers = eng.spec["n_layers"]
            heads, kv_heads = eng._cfg.num_heads, eng._cfg.kv_heads
        finally:
            eng.shutdown()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_layers
    # (rows x hidden activations in f32 are a fourth of that at most)
    scores = [r for r in hlo_audit.entry_results(text)
              if r[1] == "f32" and r[2] >= bucket * keys * heads // kv_heads]
    assert not scores, scores
    assert (compiled.memory_analysis().temp_size_in_bytes
            < heads * bucket * 4096 * 4)


def test_small_spans_stay_dense_and_heads_of_64_are_padded(one_chip):
    """The branch follows the shapes: the 64-row chunk program and the
    512-row prefill program of the same engine carry no Mosaic call; the
    hybrid cell's 512-row chunk program (heads of 64 over the 4096-key
    view) carries one, its attention layer's."""
    with hlo_audit.assume_tpu():
        eng, _ = _cell_width_engine("gpt")
        try:
            assert eng._span_impl(64, 4096) == "dense"
            assert eng._span_impl(512, 512) == "dense"
            texts = [hlo_audit.serve_lower_text(eng, kind, bucket,
                                                platform="tpu")
                     for kind, bucket in (("chunk", 64), ("prefill", 512))]
        finally:
            eng.shutdown()
        hyb, _ = _cell_width_engine("hybrid")
        try:
            assert hyb.statusz()["span_attention"] == "kernel"
            assert hyb._span_impl(512, 4096) == "kernel"
            assert hyb._span_impl(512, 512) == "dense"
            hyb._donate = True
            compiled = _chip_compile(hyb._program_builder("chunk", 512),
                                     hyb._program_specs("chunk", 512),
                                     one_chip)
        finally:
            hyb.shutdown()
    assert not any("tpu_custom_call" in t for t in texts)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "span_attention" in text
    # (the state pool is f32 and larger: whole rectangles only)
    assert not [r for r in hlo_audit.entry_results(text)
                if r[1] == "f32" and r[2] >= 512 * 4096 * 4
                and r[2] % (512 * 4096) == 0]


def test_aot_fingerprint_names_the_tiled_kernel(tmp_path):
    """The AOT store has no version but the fingerprint: the paged
    kernel's value moved with the walk, so an artifact exported under
    ``pallas-stacked`` (the slot-a-step kernel) is not found."""
    from mxnet_tpu import aot
    from mxnet_tpu.ops.attention import PAGED_TILE_TOKENS

    with hlo_audit.assume_tpu():
        eng = hlo_audit.build_serve_engine(dtype="bfloat16", block_size=8)
        try:
            fp = dict(eng._aot_base_fp(), kind="decode", bucket=4)
            status = eng.statusz()
        finally:
            eng.shutdown()
    assert fp["paged_attention"] == f"pallas-tile{PAGED_TILE_TOKENS}"
    assert status["paged_attention"] == "pallas"
    assert status["paged_tile_tokens"] == PAGED_TILE_TOKENS
    store = aot.ExportStore(str(tmp_path / "aot"))
    exported = jax.export.export(jax.jit(jnp.tanh))(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    old = dict(fp, paged_attention="pallas-stacked")
    assert store.save(old, exported)
    assert store.load(old) is not None
    assert store.load(fp) is None
    # the jnp formulation never carried the key, and still does not
    eng = hlo_audit.build_serve_engine()
    try:
        assert "paged_attention" not in eng._aot_base_fp()
        assert eng.statusz()["paged_tile_tokens"] is None
    finally:
        eng.shutdown()


# -- 2. HLO structural audits over the bench train steps --------------------

@pytest.mark.slow
def test_resnet_step_structurally_clean():
    """The bench ResNet-50 (NHWC, s2d stem) train step: 3 transposes,
    all rank-2 (the FC-head weight), zero activation transposes, and no
    layout flips around the 159 convolutions (BENCH_NOTES round-3
    audit, now enforced)."""
    trainer, placed = hlo_audit.build("resnet")
    c = _counts(hlo_audit.lower_text(trainer, placed, platform="tpu"))
    assert c["activation_transposes"] == 0, c
    assert c["transposes"] <= 3, c
    assert c["convolutions"] == 159, c


@pytest.mark.slow
def test_cifar_step_structurally_clean():
    trainer, placed = hlo_audit.build("cifar")
    c = _counts(hlo_audit.lower_text(trainer, placed, platform="tpu"))
    assert c["activation_transposes"] == 0, c
    assert c["transposes"] <= 3, c
    assert c["convolutions"] == 56, c


@pytest.mark.slow
def test_gpt_bshd_step_structurally_clean():
    """Sequence-major GPT on the REAL TPU path (flash kernels engaged
    via force_flash): at most the two tiny D-free lse row maps remain;
    the bhsd default keeps its 8-per-layer activation shuffles, so the
    delta is what BENCH_ATTN_LAYOUT=bshd buys structurally."""
    tr_b, placed_b = hlo_audit.build("gpt_bshd")
    text_b = hlo_audit.lower_text(tr_b, placed_b, platform="tpu",
                                  force_flash=True)
    c_b = _counts(text_b)
    # 2 layers x (1 fwd + 2 bwd) Mosaic kernels
    assert len(re.findall(r"tpu_custom_call", text_b)) == 6, c_b
    # the only rank>=3 transposes are the (B, S, H) -> (BH, S) lse row
    # maps in the backward kernels' prologue — no D dimension, ~KB not
    # GB of traffic
    assert c_b["activation_transposes"] <= 2, c_b

    tr_a, placed_a = hlo_audit.build("gpt")
    c_a = _counts(hlo_audit.lower_text(tr_a, placed_a, platform="tpu",
                                       force_flash=True))
    assert c_a["activation_transposes"] >= 16, c_a  # 8/layer, 2 layers


# -- 3. Collective-shape audits ---------------------------------------------

@pytest.mark.slow
def test_dp_tp_step_collectives():
    """Compiled dp x tp training step (8 virtual devices): gradient
    sync + tensor-parallel psums appear as all-reduce; nothing in this
    program should need all-to-all or collective-permute — their
    appearance means the partitioner was fed wrong shardings."""
    from jax.sharding import PartitionSpec as P

    mesh = mx.parallel.make_mesh({"dp": 2, "tp": 2})
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=16, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    tr = mx.parallel.ShardedTrainer(
        net, {"data": (8, 32), "softmax_label": (8,)}, mesh=mesh,
        batch_axis="dp",
        param_specs={"fc1_weight": P("tp", None),
                     "fc2_weight": P(None, "tp")},
        optimizer="sgd", initializer=mx.initializer.Xavier())
    placed = tr._place_batch({"data": np.zeros((8, 32), np.float32),
                              "softmax_label": np.zeros((8,), np.float32)})
    text = tr._train_step.lower(tr.params, tr.opt_state, tr.aux, placed,
                                tr._key, np.float32(1.0)).compile().as_text()
    assert len(re.findall(r"all-reduce", text)) >= 1
    assert len(re.findall(r"all-to-all", text)) == 0
    assert len(re.findall(r"collective-permute", text)) == 0


@pytest.mark.slow
def test_ring_attention_collectives():
    """Ring attention's compiled program moves K/V shards with
    collective-permute (the ICI neighbor ring) and must NOT all-gather
    the sequence — gathering would reintroduce the O(S^2/chip) memory
    the ring exists to avoid."""
    from mxnet_tpu.parallel.ring_attention import ring_attention

    mesh = mx.parallel.make_mesh({"sp": 8})
    q = jnp.zeros((1, 2, 256, 16), jnp.float32)

    def run(q):
        return ring_attention(q, q, q, mesh, axis="sp", causal=True)

    text = jax.jit(run).lower(q).compile().as_text()
    assert len(re.findall(r"collective-permute", text)) >= 1
    assert len(re.findall(r"all-gather", text)) == 0
    assert len(re.findall(r"all-to-all", text)) == 0


@pytest.mark.slow
def test_ulysses_attention_collectives():
    """Ulysses moves heads with all-to-all (two per call: scatter heads
    / gather sequence, then back) and never all-gathers the sequence."""
    from mxnet_tpu.parallel.ulysses import ulysses_attention

    mesh = mx.parallel.make_mesh({"sp": 8})
    q = jnp.zeros((1, 8, 256, 16), jnp.float32)

    def run(q):
        return ulysses_attention(q, q, q, mesh, axis="sp", causal=True)

    text = jax.jit(run).lower(q).compile().as_text()
    assert len(re.findall(r"all-to-all", text)) >= 2
    assert len(re.findall(r"all-gather", text)) == 0


# -- 4. Artifact regression gate --------------------------------------------

def _write(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)


def _run_gate(repo, threshold=0.05):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "compare_baseline.py"),
         "--repo", str(repo), "--check", "--threshold", str(threshold)],
        capture_output=True, text=True, timeout=60)


def test_regression_gate_fails_on_regression(tmp_path):
    metric = "resnet50_train_throughput"
    _write(tmp_path / "BENCH_r02.json",
           {"metric": metric, "value": 2845.0, "unit": "images/sec/chip",
            "vs_baseline": 1.14, "platform": "tpu"})
    _write(tmp_path / "BENCH_TPU_LATEST.json",
           {"metric": metric, "value": 2500.0, "unit": "images/sec/chip",
            "vs_baseline": 1.0, "platform": "tpu"})
    r = _run_gate(tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout

    # within threshold: passes
    _write(tmp_path / "BENCH_TPU_LATEST.json",
           {"metric": metric, "value": 2800.0, "unit": "images/sec/chip",
            "vs_baseline": 1.12, "platform": "tpu"})
    r = _run_gate(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr


def test_regression_gate_ignores_cpu_and_missing(tmp_path):
    metric = "resnet50_train_throughput"
    # a platform:cpu LATEST must not trip the gate even with a better
    # prior TPU record in history
    _write(tmp_path / "BENCH_r02.json",
           {"metric": metric, "value": 2845.0, "platform": "tpu"})
    _write(tmp_path / "BENCH_TPU_LATEST.json",
           {"metric": metric, "value": 5.2, "platform": "cpu",
            "best_tpu_record": {"value": 2845.0, "unit": "images/sec/chip"}})
    r = _run_gate(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr

    # empty repo: vacuous pass
    r = _run_gate(tmp_path / "nonexistent")
    assert r.returncode == 0


def test_regression_gate_on_real_repo():
    """The committed artifact set must currently satisfy its own gate."""
    r = _run_gate(REPO)
    assert r.returncode == 0, r.stdout + r.stderr


# -- 5. Bucketing recompile audit -------------------------------------------

@pytest.mark.slow
def test_bucketing_compiles_once_per_bucket():
    """Steady-state bucket switching must not recompile: each bucket's
    executor programs compile on FIRST visit only (the reference's
    bucketing promise — switch_bucket reuses the bound executor,
    bucketing_module.py:195-220; here the jit cache is the mechanism).
    A regression that defeats the cache (e.g. a fresh lambda per
    switch, a shape leaking into a python closure) turns every bucket
    revisit into a 20-40 s TPU recompile and this test catches it on
    CPU by counting XLA compile log lines."""
    import logging

    from mxnet_tpu.io import DataBatch, DataDesc

    rng = np.random.RandomState(0)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=20, output_dim=6, name="emb")
        pooled = mx.sym.mean(emb, axis=(1,))
        fc = mx.sym.FullyConnected(pooled, name="fc", num_hidden=4)
        return mx.sym.SoftmaxOutput(fc, name="softmax"), ["data"], [
            "softmax_label"]

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=16,
                                 context=mx.cpu())
    mod.bind([DataDesc("data", (8, 16))], [DataDesc("softmax_label", (8,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None,
                       optimizer_params={"learning_rate": 0.1})

    compiles = []

    class _Counter(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Finished XLA compilation"):
                compiles.append(msg)

    handler = _Counter()
    logger = logging.getLogger("jax._src.dispatch")
    prior_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.WARNING)
    import jax as _jax

    prior_log_compiles = _jax.config.jax_log_compiles
    _jax.config.update("jax_log_compiles", True)

    def run_round():
        for key in (16, 8, 4, 8, 16, 4):
            batch = DataBatch(
                [mx.nd.array(rng.randint(0, 20, (8, key)))],
                [mx.nd.array(rng.randint(0, 4, 8))],
                bucket_key=key,
                provide_data=[DataDesc("data", (8, key))],
                provide_label=[DataDesc("softmax_label", (8,))])
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()

    try:
        run_round()          # first visits: compiles expected
        warm = len(compiles)
        assert warm > 0, "counter captured nothing — logging plumbing broke"
        run_round()          # every bucket already seen
        run_round()
        assert len(compiles) == warm, (
            f"bucket revisits recompiled: {len(compiles) - warm} new "
            f"compiles after warmup:\n" + "\n".join(compiles[warm:]))
    finally:
        _jax.config.update("jax_log_compiles", prior_log_compiles)
        logger.removeHandler(handler)
        logger.setLevel(prior_level)


# -- 6. Fused attention composes with data parallelism ----------------------

@pytest.mark.slow
def test_dp_sharded_flash_gpt_parity():
    """A multi-device dp ShardedTrainer over a flash-attention GPT must
    (a) match the single-device run numerically (the op shard_maps its
    Pallas call over the batch axis via the ambient-mesh context) and
    (b) lower for TPU — GSPMD alone cannot partition Mosaic custom
    calls, which used to make multi-chip dp + fused attention refuse to
    compile."""
    vocab, seq = 53, 32

    def build(mesh, impl):
        net = mx.models.gpt(vocab, seq, num_layers=1, d_model=32,
                            num_heads=2, attn_impl=impl)
        return mx.parallel.ShardedTrainer(
            net, {"data": (8, seq), "softmax_label": (8, seq)},
            mesh=mesh, batch_axis="dp", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            input_dtypes={"data": np.int32, "softmax_label": np.float32})

    mesh2 = mx.parallel.make_mesh({"dp": 2})
    mesh1 = mx.parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    t2 = build(mesh2, "flash")       # interpreter kernels on CPU
    t1 = build(mesh1, "flash")
    p0 = t2.get_params()
    t1.set_params(p0)
    key = np.asarray(jax.device_get(t2._key))
    t1._key = jax.device_put(key, t1._replicated)
    t2._key = jax.device_put(key, t2._replicated)
    rng = np.random.RandomState(0)
    batch = {"data": rng.randint(0, vocab, (8, seq)),
             "softmax_label": rng.randint(0, vocab, (8, seq)).astype(
                 np.float32)}
    o2, o1 = t2.step(batch), t1.step(batch)
    np.testing.assert_allclose(np.asarray(o2[0]), np.asarray(o1[0]),
                               atol=2e-5, rtol=2e-4)
    p2, p1 = t2.get_params(), t1.get_params()
    for k in p0:
        np.testing.assert_allclose(p2[k], p1[k], atol=5e-5, rtol=2e-4,
                                   err_msg=k)

    # (b) the dp=8 program lowers for TPU with Mosaic kernels inside
    with hlo_audit.assume_tpu():
        net = mx.models.gpt(211, seq, num_layers=2, d_model=64,
                            num_heads=4, fused_qkv=True)
        mesh8 = mx.parallel.make_mesh({"dp": 8})
        tr8 = mx.parallel.ShardedTrainer(
            net, {"data": (16, seq), "softmax_label": (16, seq)},
            mesh=mesh8, batch_axis="dp", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            input_dtypes={"data": np.int32, "softmax_label": np.float32})
        placed = tr8._place_batch(
            {"data": np.zeros((16, seq), np.int64),
             "softmax_label": np.zeros((16, seq), np.float32)})
        text = tr8._train_step.trace(
            tr8.params, tr8.opt_state, tr8.aux, placed, tr8._key,
            np.float32(1.0)).lower(lowering_platforms=("tpu",)).as_text()
        assert len(re.findall(r"tpu_custom_call", text)) == 6  # 2 layers x 3


@pytest.mark.slow
def test_dp_sp_flash_gpt_lowers_for_tpu():
    """The combined dp x sp sequence-parallel GPT train step — flash
    kernels inside the ring schedule inside the sharded trainer — must
    lower for TPU: Mosaic custom calls present, collective-permutes
    moving K/V around the sp ring, and NO all-gather of the sequence."""
    from jax.sharding import PartitionSpec as P

    with hlo_audit.assume_tpu():
        vocab, seq = 211, 512           # shard length 128 = kernel block
        net = mx.models.gpt(vocab, seq, num_layers=2, d_model=64,
                            num_heads=4, attn_impl="flash")
        mesh = mx.parallel.make_mesh({"dp": 2, "sp": 4})
        tr = mx.parallel.ShardedTrainer(
            net, {"data": (4, seq), "softmax_label": (4, seq)},
            mesh=mesh, batch_axis="dp",
            sequence_specs={"data": P("dp", "sp"),
                            "softmax_label": P("dp", "sp")},
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            input_dtypes={"data": np.int32, "softmax_label": np.float32})
        placed = tr._place_batch(
            {"data": np.zeros((4, seq), np.int64),
             "softmax_label": np.zeros((4, seq), np.float32)})
        text = tr._train_step.trace(
            tr.params, tr.opt_state, tr.aux, placed, tr._key,
            np.float32(1.0)).lower(lowering_platforms=("tpu",)).as_text()
        assert len(re.findall(r"tpu_custom_call", text)) >= 3
        assert len(re.findall(r"collective_permute", text)) >= 2
        assert len(re.findall(r"all_gather", text)) == 0


# -- 4. Serve program-family audits (perf-attribution gate) -----------------
# The serve-side analog of layer 2: lower the EXACT bucketed programs
# serve.Engine dispatches (via hlo_audit.build_serve_engine +
# engine._program_builder) and pin dot_general / transpose counts plus
# cost_analysis() flops, so a lowering regression in the decode hot
# path — an extra gather-induced transpose, a duplicated matmul, a
# flops blow-up — fails CI on CPU alone (flops as a band around the
# analytic count: see SERVE_FLOPS_TOL).  Counts measured identical
# under cpu and --tpu lowering at this config (no Pallas at these tiny
# shapes), so the CPU pins audit the real TPU program structure too.

SERVE_PINS = {
    # (kind, bucket): transposes, act_transposes, dot_generals, and
    # cost_analysis() flops over the engine's analytic count
    # (Engine._analytic_cost: flops.gpt_token_flops / gpt_prefill_flops
    # over the padded shapes; None: a pure copy program, no matmul)
    ("prefill", 8):     (17, 4, 17, 1.013),
    ("chunk", 8):       (17, 4, 17, 1.037),
    ("decode", 4):      (13, 0, 17, 1.100),
    ("draft", 4):       (16, 0, 20, 1.278),
    ("draft_chunk", 8): (9, 2, 9, 0.996),
    ("verify", 4):      (17, 4, 17, 1.100),
    ("restore", 4):     (0, 0, 0, None),
}
# How far a family's flops ratio may sit from its pin.  XLA's count is
# the matmuls (the analytic count, which moves with the audit engine's
# geometry and not with jax) plus whatever the installed jax bills for
# softmax, layer norm and the other elementwise work, and that part has
# moved by 2-5 % of the total between jax releases (verify-4 read 824608
# under the jax these pins were first taken with and 801848 under 0.4.x
# today, draft-4 106390 and 101064).  The smallest structural slip worth
# catching, one more d_model x d_model projection in decode, is 6 %.
SERVE_FLOPS_TOL = 0.055


@pytest.fixture(scope="module")
def serve_audit_engine():
    eng = hlo_audit.build_serve_engine()
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("kind,bucket", sorted(SERVE_PINS))
def test_serve_program_op_counts(serve_audit_engine, kind, bucket):
    """Each serve program family keeps its pinned op structure."""
    transposes, act, dots, _ = SERVE_PINS[(kind, bucket)]
    c = _counts(hlo_audit.serve_lower_text(serve_audit_engine, kind,
                                           bucket))
    assert c["dot_generals"] == dots, (kind, c)
    assert c["transposes"] == transposes, (kind, c)
    assert c["activation_transposes"] == act, (kind, c)
    assert c["convolutions"] == 0 and c["all_to_alls"] == 0, (kind, c)


@pytest.mark.parametrize("kind,bucket", sorted(SERVE_PINS))
def test_serve_program_cost_flops(serve_audit_engine, kind, bucket):
    """cost_analysis() flops — the numbers the engine's perf cost
    table captures at resolve time — stay in a band around the
    engine's own analytic count for the family."""
    flops = hlo_audit.serve_cost_flops(serve_audit_engine, kind, bucket)
    assert flops is not None, (kind, bucket)
    analytic, _ = serve_audit_engine._analytic_cost(kind, bucket)
    pin = SERVE_PINS[(kind, bucket)][3]
    if pin is None:
        # restore scatters host blocks into the cache: index arithmetic
        # only, and the analytic table has no flops for it either
        assert analytic is None and flops < 1024, (kind, flops)
        return
    ratio = flops / analytic
    assert abs(ratio / pin - 1.0) < SERVE_FLOPS_TOL, (kind, flops,
                                                      analytic, ratio)


def test_analytic_flops_cross_check(serve_audit_engine):
    """flops.gpt_token_flops / gpt_prefill_flops (the analytic fallback
    and the MFU denominators surfaced in docs) agree with the XLA
    cost_analysis() numbers to within model-shape slop: the analytic
    count ignores softmax/layernorm flops while cost_analysis bills
    them, so the ratio analytic/measured sits in a tight band below 1
    at tiny d_model and approaches 1 as matmuls dominate."""
    from mxnet_tpu import flops as F

    spec = serve_audit_engine.spec
    d_model = spec["d_model"]
    head_dim, kvh = spec["head_dim"], spec["kv_heads"]
    heads = d_model // head_dim
    # decode attends over the PADDED paged context (the whole table)
    ctx = serve_audit_engine.max_model_len

    per_tok = F.gpt_token_flops(
        n_layers=spec["n_layers"], d_model=d_model, num_heads=heads,
        head_dim=head_dim, kv_heads=kvh, vocab=spec["vocab"],
        context=ctx)
    measured = hlo_audit.serve_cost_flops(serve_audit_engine,
                                          "decode", 4)
    ratio = (4 * per_tok) / measured
    assert 0.5 < ratio < 1.5, (4 * per_tok, measured)

    pre = F.gpt_prefill_flops(
        n_layers=spec["n_layers"], d_model=d_model, num_heads=heads,
        head_dim=head_dim, kv_heads=kvh, vocab=spec["vocab"],
        seq_len=8)
    measured = hlo_audit.serve_cost_flops(serve_audit_engine,
                                          "prefill", 8)
    ratio = pre / measured
    assert 0.5 < ratio < 1.5, (pre, measured)
