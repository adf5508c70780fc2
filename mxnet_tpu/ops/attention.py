"""Attention-era operators: LayerNorm, GELU, fused multi-head attention.

Beyond-parity additions (the 2016 reference predates transformers) that
make the Pallas flash-attention kernel (``ops/flash_attention.py``) and
a GPT-style model zoo entry (``models/transformer.py``) available from
the Symbol/NDArray frontends like any reference op.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..lint.annotations import hot_path
from ..param import Params, field
from . import pallas_util
from .op import OpDef, register_op, register_simple_op


def score_scale(head_dim):
    """1/sqrt(head_dim) as an f32 scalar.  ``np.sqrt`` returns a NumPy
    float64, which is not weak-typed: under ``jax_enable_x64`` it
    promotes the whole score tensor to f64, which a TPU emulates."""
    return np.float32(1.0 / np.sqrt(head_dim))


# Ambient SPMD context for the fused-attention op: Mosaic kernels cannot
# be auto-partitioned by GSPMD, so when a FlashAttention op runs inside
# a multi-device sharded program the kernel call must be wrapped in a
# shard_map over the batch axis (attention is embarrassingly parallel
# across data-parallel shards).  ShardedTrainer sets this around its
# traced graph calls; single-device programs never touch it.
_SPMD_ATTN = contextvars.ContextVar("spmd_attention", default=None)


@contextlib.contextmanager
def spmd_attention(mesh, batch_axis, seq_axis=None):
    """While active, FlashAttention ops adapt to the sharded program:

    - ``seq_axis`` sharded (sequence parallelism): the op routes to a
      sharded-attention schedule over that axis — ring (default) or
      Ulysses per the op's ``sp_impl`` param.  Per-shard local
      attention would silently attend within shards only, so SOME
      global schedule is required for correctness, whatever impl.
    - otherwise, batch sharded + Pallas path: the kernel call is
      wrapped in ``shard_map(..., in_specs=P(batch_axis, ...))`` so
      fused attention composes with data parallelism."""
    token = _SPMD_ATTN.set((mesh, batch_axis, seq_axis))
    try:
        yield
    finally:
        _SPMD_ATTN.reset(token)


# -- LayerNorm ---------------------------------------------------------------
class LayerNormParam(Params):
    axis = field(int, default=-1)
    eps = field(float, default=1e-5)


@register_op("LayerNorm", aliases=("layernorm",))
class LayerNormOp(OpDef):
    """Normalize over one axis with learnable scale/shift.

    Statistics are computed in f32 regardless of input dtype (bf16-safe,
    like the fused BatchNorm in ops/nn.py); XLA fuses the whole op into
    its neighbors.
    """

    param_cls = LayerNormParam

    def list_arguments(self, params):
        return ["data", "gamma", "beta"]

    def infer_shape(self, params, in_shapes):
        d = in_shapes[0]
        if d is None:
            raise ValueError("LayerNorm: data shape unknown")
        c = (d[params.axis % len(d)],)
        return [tuple(d), c, c], [tuple(d)], []

    def forward(self, params, inputs, aux, train, key):
        x, gamma, beta = inputs
        axis = params.axis % x.ndim
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axis, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=axis, keepdims=True)
        inv = jax.lax.rsqrt(var + params.eps)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        y = (xf - mean) * inv * gamma.astype(jnp.float32).reshape(shape) \
            + beta.astype(jnp.float32).reshape(shape)
        return [y.astype(x.dtype)], []


class RMSNormParam(Params):
    axis = field(int, default=-1)
    eps = field(float, default=1e-5)


@register_op("RMSNorm", aliases=("rmsnorm",))
class RMSNormOp(OpDef):
    """Root-mean-square normalization (llama-style LayerNorm without
    the mean subtraction or shift): y = x / rms(x) * gamma.  Stats in
    f32 like LayerNorm; XLA fuses it into its neighbors."""

    param_cls = RMSNormParam

    def list_arguments(self, params):
        return ["data", "gamma"]

    def infer_shape(self, params, in_shapes):
        d = in_shapes[0]
        if d is None:
            raise ValueError("RMSNorm: data shape unknown")
        c = (d[params.axis % len(d)],)
        return [tuple(d), c], [tuple(d)], []

    def forward(self, params, inputs, aux, train, key):
        x, gamma = inputs
        axis = params.axis % x.ndim
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        y = xf * jax.lax.rsqrt(ms + params.eps) \
            * gamma.astype(jnp.float32).reshape(shape)
        return [y.astype(x.dtype)], []


register_simple_op(
    "gelu",
    lambda x: (0.5 * x.astype(jnp.float32)
               * (1.0 + jax.lax.erf(x.astype(jnp.float32)
                                    / np.float32(np.sqrt(2.0))))
               ).astype(x.dtype),
    nin=1)

# f32-activation convention like gelu: bf16 models must compute the
# swiglu gate identically in the training graph and the KV-cache
# decoder or near-tie logits round differently between them
register_simple_op(
    "silu",
    lambda x: (x.astype(jnp.float32)
               * jax.nn.sigmoid(x.astype(jnp.float32))).astype(x.dtype),
    nin=1)


# -- fused multi-head attention ----------------------------------------------
class FlashAttentionParam(Params):
    causal = field(bool, default=False)
    # sliding-window (local) attention radius; 0 = full attention
    # (negative values rejected at the kernel entry)
    window = field(int, default=0)
    block_q = field(int, default=512)
    block_k = field(int, default=512)
    impl = field(str, default="auto", enum=("auto", "flash", "xla"))
    layout = field(str, default="bhsd", enum=("bhsd", "bshd"))
    # sequence-parallel variant when the ambient seq axis is sharded:
    # ring (ppermute K/V shards; any head count) or ulysses (two
    # all-to-alls re-shard seq<->heads; needs heads % sp == 0)
    sp_impl = field(str, default="ring", enum=("ring", "ulysses"))


@register_op("FlashAttention", aliases=("flashattention",))
class FlashAttentionOp(OpDef):
    """softmax(Q K^T / sqrt(D)) V over (batch, heads, seq, head_dim)
    [layout='bhsd'] or (batch, seq, heads, head_dim) [layout='bshd',
    sequence-major — no activation transpose feeding the kernel].

    K/V may carry FEWER heads than Q (grouped-query / multi-query
    attention; q heads must be a multiple of kv heads): native in the
    Pallas kernels under layout='bshd' (one shared K/V head streamed
    per group), expanded under 'bhsd', the dense fallback, and the
    sequence-parallel schedules.  ``window`` > 0 adds sliding-window
    locality — including under sequence parallelism (ring masks with
    global positions and bounds its steps to the band; ulysses sees the
    full sequence after its all-to-all).  On TPU with fitting block
    sizes this lowers to the fused
    Pallas kernel (forward + custom-VJP backward); elsewhere it runs
    the XLA dense formulation.  Differentiable either way.
    """

    param_cls = FlashAttentionParam

    def list_arguments(self, params):
        return ["query", "key", "value"]

    def infer_shape(self, params, in_shapes):
        q = in_shapes[0]
        kv = in_shapes[1] or in_shapes[2]
        if q is None and kv is None:
            raise ValueError("FlashAttention: input shapes unknown")
        if q is None:
            q = kv
        if kv is None:
            kv = q          # MHA default; GQA needs k/v shapes known
        return [tuple(q), tuple(kv), tuple(kv)], [tuple(q)], []

    def forward(self, params, inputs, aux, train, key):
        q, k, v = inputs
        from .flash_attention import flash_attention

        spmd = _SPMD_ATTN.get()
        mesh = batch_ax = None
        batch_sharded = False
        if spmd is not None:
            mesh, batch_ax, seq_ax = spmd
            mshape = dict(mesh.shape)
            batch_sharded = mshape.get(batch_ax, 1) > 1
            if seq_ax is not None and mshape.get(seq_ax, 1) > 1:
                # sequence-parallel program: global attention over the
                # sharded sequence REQUIRES a sharded schedule — local
                # per-shard attention would be silently wrong
                h_ax = 2 if params.layout == "bshd" else 1
                if k.shape[h_ax] != q.shape[h_ax]:
                    # grouped-query K/V under sequence parallelism:
                    # validate for a clean error here; ring streams the
                    # REDUCED K/V shards natively (bshd — bhsd expands
                    # inside the kernel call), ulysses keeps K/V native
                    # when kv heads divide the sp axis and expands at
                    # entry otherwise
                    from .flash_attention import gqa_group
                    gqa_group(q.shape[h_ax], k.shape[h_ax])
                if params.sp_impl == "ulysses":
                    from ..parallel.ulysses import ulysses_attention \
                        as sp_attention
                else:
                    from ..parallel.ring_attention import ring_attention \
                        as sp_attention

                out = sp_attention(
                    q, k, v, mesh, axis=seq_ax, causal=params.causal,
                    impl=params.impl, block_q=params.block_q,
                    block_k=params.block_k, layout=params.layout,
                    batch_axis=batch_ax if batch_sharded else None,
                    window=params.window)
                return [out], []

        seq_axis = 1 if params.layout == "bshd" else 2
        S = q.shape[seq_axis]
        from .flash_attention import flash_eligible
        use_flash = params.impl == "flash"
        if params.impl == "auto" and pallas_util.on_tpu():
            use_flash = flash_eligible(S, S, params.block_q, params.block_k)
            if not use_flash:
                # trace-time only: say which implementation a TPU program
                # got when the geometry declines the kernel
                logging.getLogger(__name__).warning(
                    "FlashAttention impl=auto resolved to dense XLA on a "
                    "TPU: seq_len %d admits no Mosaic-scale block <= "
                    "(%d, %d)", S, params.block_q, params.block_k)
        if use_flash:
            # wrap only when the BATCH axis is actually sharded: a
            # dp=1 x tp=N mesh must not funnel tp-sharded activations
            # through a batch-replicated shard_map (redundant compute +
            # resharding); with dp=1 the kernel call is single-program
            # per GSPMD and needs no wrap.  (A custom_partitioning rule
            # on flash_attention would decouple this from the trainer
            # entirely — candidate future work.)
            if batch_sharded:
                # data-parallel sharded program: run the kernel per
                # batch shard under shard_map (GSPMD cannot partition a
                # Mosaic custom call on its own)
                from jax.sharding import PartitionSpec

                spec = PartitionSpec(batch_ax, *([None] * (q.ndim - 1)))

                def _local(q_s, k_s, v_s):
                    return flash_attention(q_s, k_s, v_s,
                                           causal=params.causal,
                                           block_q=params.block_q,
                                           block_k=params.block_k,
                                           layout=params.layout,
                                           window=params.window)

                out = jax.shard_map(_local, mesh=mesh,
                                    in_specs=(spec, spec, spec),
                                    out_specs=spec,
                                    check_vma=False)(q, k, v)
                return [out], []
            out = flash_attention(q, k, v, causal=params.causal,
                                  block_q=params.block_q,
                                  block_k=params.block_k,
                                  layout=params.layout,
                                  window=params.window)
            return [out], []
        scale = score_scale(q.shape[-1])
        h_ax = 2 if params.layout == "bshd" else 1
        if k.shape[h_ax] != q.shape[h_ax]:
            # grouped-query attention through the dense path: expand K/V
            from .flash_attention import gqa_group
            rep = gqa_group(q.shape[h_ax], k.shape[h_ax])
            k = jnp.repeat(k, rep, axis=h_ax)
            v = jnp.repeat(v, rep, axis=h_ax)
        if params.layout == "bshd":
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        pos_q = jnp.arange(S)[:, None]
        pos_k = jnp.arange(S)[None, :]
        keep = None
        if params.causal:
            keep = pos_q >= pos_k
        if params.window < 0:
            raise ValueError(
                f"FlashAttention: window must be >= 0 "
                f"(got {params.window})")
        if params.window:
            band = pos_q - pos_k < params.window
            if not params.causal:
                band = jnp.logical_and(band, pos_k - pos_q < params.window)
            keep = band if keep is None else jnp.logical_and(keep, band)
        if keep is not None:
            s = jnp.where(keep, s, jnp.asarray(-jnp.inf, s.dtype))
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        if params.layout == "bshd":
            return [jnp.einsum("bhqk,bkhd->bqhd", p, v)], []
        return [jnp.einsum("bhqk,bhkd->bhqd", p, v)], []


# -- span attention (serving) ------------------------------------------------
# The Mosaic span kernel's tiles (ops/pallas_span_attention.py): query rows
# and key positions a step of its walk folds, and the key positions of one
# kv head it keeps resident in VMEM.  Constants from the chip, not knobs.
# One v5e chip, a layer's call at 32 / 8 heads x 128 in bfloat16, ms
# (PERF.md, PR 32; "dense" is masked_attention's other branch, alone):
#   rows x keys, first position     dense  (256,1024) (512,512) (512,1024)
#   2048 x 4096, 0    (2033 real)    7.93    0.526     0.512     0.527
#   2048 x 4096, 2033 (1800 real)    7.92    0.987     1.030     0.997
#   2048 x 2048, 0    (2000 real)    1.46    0.524     0.512     0.520
#   1024 x 1024, 0    ( 900 real)    0.393   0.194     0.181     0.192
# (512, 2048) and (256, 2048) read 0.61 / 1.08 / 0.61: a wider tile wastes
# more above the diagonal than its fewer steps save.
SPAN_BLOCK_Q = 512
SPAN_BLOCK_K = 512
SPAN_RESIDENT_TOKENS = 4096
# A span whose score rectangle (rows x keys, a head) is smaller stays
# dense: verify's k+1 rows, the low chunk and prefill buckets.  Dense
# against the kernel, ms: 128 x 4096 0.226 / 0.081, 64 x 4096 0.047 /
# 0.069; whole prompts 1024 x 1024 0.393 / 0.181, 512 x 512 0.034 / 0.071,
# 256 x 256 0.009 / 0.037 (the dense form's cost follows the rectangle
# it writes out, the kernel's has a floor of a kv head's K/V fetch)
SPAN_KERNEL_MIN_SCORES = 128 * 4096


def span_kernel_eligible(T, S, head_dim, block_q=None, block_k=None,
                         resident=None, min_scores=None):
    """Whether the span kernel serves ``T`` rows over ``S`` keys: heads
    that are whole 128-lane tiles (a head is a column block of the
    operands as they lie in memory) or half of one (64: zero-padded in
    front of the call), a score rectangle of at least ``min_scores``, and
    shapes its blocks tile."""
    bq = min(SPAN_BLOCK_Q, T) if block_q is None else block_q
    bk = min(SPAN_BLOCK_K, S) if block_k is None else block_k
    res = min(SPAN_RESIDENT_TOKENS, S) if resident is None else resident
    if min_scores is None:
        min_scores = SPAN_KERNEL_MIN_SCORES
    return ((head_dim % 128 == 0 or head_dim == 64) and T * S >= min_scores
            and bq % 16 == 0 and T % bq == 0
            and bk % 128 == 0 and res % bk == 0 and S % res == 0)


def resolve_span_impl(T, S, head_dim):
    """The branch :func:`masked_attention` traces for a span of ``T`` rows
    over ``S`` keys of ONE request: ``"kernel"`` or ``"dense"``.  Backend
    and shapes only (``resolve_paged_impl``'s rule: off the chip the
    dense form runs, the interpreter is for the kernel's own tests)."""
    if pallas_util.on_tpu() and span_kernel_eligible(T, S, head_dim):
        return "kernel"
    return "dense"


def span_kv_tiles(T, S, start, n_valid, window=0, impl="kernel", block=1):
    """``(visited, table)``: the ``(SPAN_BLOCK_Q, SPAN_BLOCK_K)`` tiles
    one head of a span's attention computes, over the tiles of its ``(T,
    S)`` rectangle.  Host arithmetic that mirrors the kernel's walk (a
    test holds it to the mask); the dense branch computes them all.
    ``block``: the mask's block length (a row sees to its block's end)."""
    bq, bk = min(SPAN_BLOCK_Q, T), min(SPAN_BLOCK_K, S)
    table = -(-T // bq) * -(-S // bk)
    if impl != "kernel":
        return table, table
    visited, end = 0, start + n_valid
    for p0 in range(start, min(start + T, end), bq):
        hi = min(-(-min(p0 + bq, end) // block) * block, S)
        lo = max(p0 - window + 1, 0) if window else 0
        visited += max(-(-hi // bk) - lo // bk, 0)
    return visited, table


def _span_keep(start, T, S, window, block=1):
    """``(..., T, S)``: row ``t`` at position ``start + t`` keeps the keys
    at positions ``<= `` its own (and inside its window); with ``block``
    > 1 those before its block's end, ``< (pos // block + 1) * block``."""
    pos = jnp.asarray(start)[..., None] + jnp.arange(T)
    spos = jnp.arange(S)[(None,) * pos.ndim]
    if block > 1:
        keep = spos < (pos[..., None] // block + 1) * block
    else:
        keep = spos <= pos[..., None]               # causal, self included
    if window:
        keep = jnp.logical_and(keep, spos > pos[..., None] - window)
    return keep


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret",
                                             "block"))
def _span_kernel_call(q, k, v, start, n_valid, *, scale, window, interpret,
                      block=1):
    # jitted so that the layers of a program share ONE trace of the
    # kernel (the paged kernel's cost 3-6 s of tracing over five decode
    # programs at PR 30; the prefill and chunk ladders are 25)
    from .pallas_span_attention import span_attention_kernel

    return span_attention_kernel(q, k, v, start, n_valid, scale,
                                 window=window, interpret=interpret,
                                 **({"block": block} if block > 1 else {}))


def masked_attention(q, k, v, start, scale, window=0, n_valid=None,
                     mesh=None, head_axis=None, block=1):
    """Causal grouped-query attention of a span of query rows over key
    rows: the span attention of the serving programs (a whole prompt; a
    chunk or verify rows over the table's gathered view), whatever
    leading axes the operands share.

    ``q (..., T, Hq, Dh)``, row ``t`` at position ``start + t``
    (``start (...)`` int, traced or not); ``k``/``v (..., S, Hkv, Dh)``,
    row ``s`` at position ``s``, kv head g serving q heads ``[g*group,
    (g+1)*group)`` as in :func:`paged_attention`.  A row attends to the
    keys at positions ``<=`` its own and, with ``window``, ``>`` its own
    minus ``window``.  ``scale`` multiplies the scores (an f32 scalar).
    ``n_valid``: the count of real rows, where the caller knows it; the
    rows past it are padding whose outputs nobody reads (the kernel
    skips their tiles).  Softmax in f32.  Returns ``(..., T, Hkv, group,
    Dh)`` in q's dtype: the q heads in their order, one reshape from
    ``(..., T, Hq * Dh)``.

    Two branches, chosen by :func:`resolve_span_impl` from the backend
    and the shapes: the Mosaic streaming-softmax walk over the key tiles
    the span can see (``ops/pallas_span_attention.py``), or the dense
    form below, which builds the mask and materialises the scores.
    ``mesh``/``head_axis``: as for :func:`paged_attention`; GSPMD cannot
    partition a Mosaic call, so under a mesh the kernel runs per head
    shard inside a ``shard_map``.  ``block`` > 1: the mask is
    block-causal, a row sees every key before its block's end (``start``
    and the real rows whole blocks of it)."""
    from .flash_attention import gqa_group

    T, Hq, Dh = q.shape[-3:]
    S, Hkv = k.shape[-3:-1]
    group = gqa_group(Hq, Hkv)
    if q.ndim == 3 and resolve_span_impl(T, S, Dh) == "kernel":
        kernel = functools.partial(
            _span_kernel_call, scale=np.float32(scale), window=int(window),
            interpret=not pallas_util.on_tpu(),
            **({"block": int(block)} if block > 1 else {}))
        args = (q, k, v, jnp.asarray(start, jnp.int32),
                jnp.asarray(T if n_valid is None else n_valid, jnp.int32))
        if mesh is None:
            return kernel(*args)
        from jax.sharding import PartitionSpec as P

        heads = P(None, head_axis, None)
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=(heads,) * 3 + (P(), P()),
            out_specs=P(None, head_axis, None, None),
            check_vma=False)(*args)
    qg = q.reshape(q.shape[:-2] + (Hkv, group, Dh))
    sc = jnp.einsum("...qkgd,...skd->...kgqs", qg, k) * scale
    keep = _span_keep(start, T, S, window, block)
    sc = jnp.where(keep[..., None, None, :, :], sc,
                   jnp.asarray(-jnp.inf, sc.dtype))
    pr = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("...kgqs,...skd->...qkgd", pr, v)


# -- paged attention (serving) -----------------------------------------------
# Positions of a row's context the Mosaic decode kernels fold in one
# step of their walk: one score product, one softmax update and one
# p x V product per tile.  A constant from the chip, not a knob: at 16
# tokens a block 16 table slots make a tile.  On one v5e chip, 16 rows
# at 1.5-3.9 k tokens of context, 8 kv heads x 128, a layer: 128
# positions 309 us, 256: 271 us, 512: 276 us (the slot-a-step walk
# before: 2,328 us); the packed kernel and rows of a few tokens lose 4-9 %
# at 256 against 128, a tile's tail being read whole (PERF.md, PR 30).
PAGED_TILE_TOKENS = 256


def paged_tile_slots(block_size):
    """Table slots in one tile of the paged decode kernels' walk."""
    return max(1, PAGED_TILE_TOKENS // int(block_size))


def paged_eligible(block_size, head_dim):
    """Whether the Mosaic kernel's tile shapes are worth lowering for
    this cache geometry: head_dim should fill MXU/VPU lanes (multiples
    of 8 keep Mosaic's f32 tiling happy; 128 is the sweet spot).  The
    kernel folds ``PAGED_TILE_TOKENS`` positions a step whatever the
    block holds, but every block of a tile is a DMA of its own, so a
    1-token block would issue 256 copies a tile."""
    return head_dim % 8 == 0 and block_size >= 4


def resolve_paged_impl(block_size, head_dim, impl=None):
    """The implementation :func:`paged_attention` will trace for this
    cache geometry — ``"pallas"`` or ``"jnp"``.  Pure host logic (env +
    backend + eligibility), no Pallas import: callers that key compiled
    artifacts on the choice (serve.Engine's AOT fingerprint — an
    exported program bakes the lowering and replays it regardless of
    the env at load time) consult this without touching
    ``jax.experimental.pallas``."""
    if impl is None:
        impl = os.environ.get("MXTPU_PAGED_ATTENTION") or "auto"
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"paged_attention: impl must be auto|pallas|jnp "
                         f"(got {impl!r})")
    if impl == "jnp":
        return "jnp"
    if impl == "pallas" or (pallas_util.on_tpu()
                            and paged_eligible(block_size, head_dim)):
        return "pallas"
    return "jnp"


@hot_path
def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    window=0, scale=None, k_scale=None, v_scale=None,
                    impl=None, mesh=None, head_axis=None, layer=None,
                    flat_heads=None):
    """Single-token decode attention over a paged KV-cache.

    The serving engine (``mxnet_tpu/serve``) keeps one fixed
    device-resident cache carved into fixed-size blocks; each request
    owns a per-request *block table* mapping its logical token
    positions onto physical blocks.  Each query attends against its
    own context through the tables — the vLLM-style paged-attention
    formulation.  On TPU this dispatches to the Mosaic kernel in
    ``ops/pallas_paged_attention.py`` that walks each row's live
    context a tile of ``PAGED_TILE_TOKENS`` positions at a time, the
    tile's blocks copied from HBM through the table, with f32
    accumulation (``impl="auto"`` default, overridable per
    process via ``MXTPU_PAGED_ATTENTION=auto|pallas|jnp`` — the same
    selection shape as ``flash_attention``); everywhere else it runs
    the XLA gather + masked softmax below, which doubles as the
    kernel's parity oracle.

    Args:
      q: (B, Hq, Dh) — one query token per sequence.
      k_cache/v_cache: (num_blocks, block_size, Hkv, Dh) physical
        cache, or with ``layer`` the stacked (L, num_blocks,
        block_size, Hkv, Dh) pool of every layer.  Hq must be a
        multiple of Hkv (grouped-query native: kv head g serves q
        heads [g*group, (g+1)*group)).
      block_tables: (B, W) int32 physical block ids per sequence, in
        logical order; rows pad with the null block (id 0) past the
        sequence's last block.
      context_lens: (B,) int32 — valid cache entries per sequence
        (the current token's K/V already written).  Padded table
        entries sit beyond the context and are masked out.  A row with
        0 valid entries (a dead slot in a bucketed batch) returns
        zeros — never a fully-masked softmax's NaN.
      window: sliding-window radius (0 = full attention), matching
        the FlashAttention op's ``window`` semantics at decode: the
        query at position L-1 sees positions > L-1-window only.
      scale: score scale; default 1/sqrt(Dh).
      k_scale/v_scale: per-slot-per-head f32 dequantization scales
        (num_blocks, block_size, Hkv) for int8 K/V caches
        (``MXTPU_SERVE_KV_DTYPE=int8``): the cache entry is
        ``int8 * scale``.  Pass both or neither; stacked over the
        layers like the caches when ``layer`` is given.
      impl: "auto" (kernel on TPU), "pallas", or "jnp"; default the
        ``MXTPU_PAGED_ATTENTION`` env var, else "auto".
      mesh/head_axis: the device mesh of the enclosing sharded jit and
        the mesh axis the q/kv HEAD dimension is split over (None =
        replicated).  GSPMD cannot partition a Mosaic custom call, so
        under a mesh the kernel runs per head shard inside a
        ``shard_map`` (heads are independent; a contiguous split keeps
        every q-head group with its kv head).  The jnp formulation is
        plain XLA and partitions on its own.
      layer: static index of the layer to read from stacked caches
        (None = the caches are one layer's, 4-D).  The layer is
        addressed in place — by the kernel's DMA index, or as one more
        index of the jnp gather — and never sliced out first: a slice
        of the stack that feeds a custom call is a copy of that
        layer's whole pool (serve/programs.py passes its stack as is).
      flat_heads: the caches are FLAT in their minor axis, ``(L,
        num_blocks, block_size, flat_heads * Dh)``, every kv head of a
        position side by side (serve/hybrid.py's layout where ``(Hkv,
        Dh)`` does not fill a tile: an ``(8, 64)`` bfloat16 tile is
        padded fourfold on the chip, a ``(2, 128)`` one eightfold, side
        by side nothing is).  Takes
        ``layer``; no window, int8 scales or mesh.

    Returns (B, Hq, Dh) attention output in q's dtype.
    """
    B, Hq, Dh = q.shape
    if flat_heads is not None:
        return _paged_attention_flat(q, k_cache, v_cache, block_tables,
                                     context_lens, layer, int(flat_heads),
                                     scale, impl, window, k_scale, mesh)
    if (layer is None) != (k_cache.ndim == 4):
        raise ValueError("paged_attention: stacked (L, num_blocks, "
                         "block_size, Hkv, Dh) caches take `layer`, "
                         "a single layer's 4-D caches do not")
    bs, Hkv = k_cache.shape[-3:-1]
    if window < 0:
        raise ValueError(f"paged_attention: window must be >= 0 "
                         f"(got {window})")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged_attention: k_scale and v_scale must be "
                         "given together")
    from .flash_attention import gqa_group
    group = gqa_group(Hq, Hkv)
    if resolve_paged_impl(bs, Dh, impl) == "pallas":
        # deferred import: impl="jnp" is the escape hatch when the
        # kernel (or jax.experimental.pallas itself) misbehaves, so it
        # must not require the Pallas modules to import
        from .pallas_paged_attention import paged_attention_kernel

        def kernel(q, k_cache, v_cache, block_tables, context_lens,
                   *scales):
            ks, vs = scales or (None, None)
            return paged_attention_kernel(
                q, k_cache, v_cache, block_tables, context_lens,
                window=window, scale=scale, k_scale=ks, v_scale=vs,
                layer=layer)

        scales = () if k_scale is None else (k_scale, v_scale)
        args = (q, k_cache, v_cache, block_tables, context_lens) + scales
        if mesh is None:
            return kernel(*args)
        from jax.sharding import PartitionSpec as P

        q_spec = P(None, head_axis, None)
        # the caches' (and scales') leading axes — blocks and slots,
        # under a layer axis when stacked — stay whole on every shard
        lead = (None,) * (k_cache.ndim - 2)
        specs = ((q_spec,) + (P(*lead, head_axis, None),) * 2
                 + (P(), P()) + (P(*lead, head_axis),) * len(scales))
        return jax.shard_map(kernel, mesh=mesh, in_specs=specs,
                             out_specs=q_spec, check_vma=False)(*args)
    scale = score_scale(Dh) if scale is None else np.float32(scale)
    S = block_tables.shape[1] * bs
    # (B, W, bs, Hkv, Dh) -> (B, S, Hkv, Dh): each row's logical view,
    # ONE gather (the layer of a stacked cache is an index of it)
    at = block_tables if layer is None else (layer, block_tables)
    k = k_cache[at].reshape(B, S, Hkv, Dh)
    v = v_cache[at].reshape(B, S, Hkv, Dh)
    if k_scale is not None:
        # int8 blocks dequantize through the same gathered view; the
        # scale arrays ride the same block tables (serve/engine.py owns
        # them alongside k_cache/v_cache)
        k = (k.astype(jnp.float32)
             * k_scale[at].reshape(B, S, Hkv)[..., None]
             ).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * v_scale[at].reshape(B, S, Hkv)[..., None]
             ).astype(q.dtype)
    qg = q.reshape(B, Hkv, group, Dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k) * scale
    pos = jnp.arange(S)[None, :]
    keep = pos < context_lens[:, None]
    if window:
        keep = jnp.logical_and(keep,
                               pos > context_lens[:, None] - 1 - window)
    s = jnp.where(keep[:, None, None, :], s,
                  jnp.asarray(-jnp.inf, s.dtype))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    # an all-masked row's softmax is 0/0 = NaN: a bucketed batch's dead
    # slot (context_lens == 0) must yield zeros, or one padded row
    # poisons MXTPU_NUMERIC_WATCH's logits-finite flag for the batch
    out = jnp.where((context_lens > 0)[:, None, None, None], out,
                    jnp.zeros((), out.dtype))
    return out.reshape(B, Hq, Dh)


def packed_eligible(kv_heads, head_dim):
    """Whether the packed kernel can serve a flat cache of this geometry:
    whole kv heads fill the 128 lanes (head size 128: one, 64: two, 32:
    four) and the kv heads divide into such groups."""
    return (head_dim <= 128 and 128 % head_dim == 0
            and kv_heads % (128 // head_dim) == 0)


def flat_paged_impl(block_size, kv_heads, head_dim, impl=None):
    """``resolve_paged_impl`` for a flat stacked cache: the packed kernel
    serves it only where whole kv heads fill the lanes."""
    if (resolve_paged_impl(block_size, head_dim, impl) == "pallas"
            and packed_eligible(kv_heads, head_dim)):
        return "pallas"
    return "jnp"


def _paged_attention_flat(q, k_cache, v_cache, block_tables, context_lens,
                          layer, Hkv, scale, impl, window, k_scale, mesh):
    B, Hq, Dh = q.shape
    if (window or k_scale is not None or mesh is not None or layer is None
            or k_cache.ndim != 4):
        raise ValueError("paged_attention: a flat cache is stacked (L, "
                         "num_blocks, block_size, Hkv * Dh) and takes "
                         "`layer`, no window, no int8 scales and no mesh")
    bs, flat = k_cache.shape[-2:]
    if flat != Hkv * Dh or Hq % Hkv:
        raise ValueError(f"paged_attention: a flat cache of width {flat} "
                         f"does not hold kv heads of size {Dh} that "
                         f"divide {Hq} query heads")
    if flat_paged_impl(bs, Hkv, Dh, impl) == "pallas":
        from .pallas_paged_attention import paged_attention_packed_kernel

        return paged_attention_packed_kernel(
            q, k_cache, v_cache, block_tables, context_lens, layer,
            scale=scale)
    # the XLA formulation is the stacked one's, over the same bytes seen
    # with the heads apart (off the chip a reshape costs nothing)
    split = k_cache.shape[:3] + (Hkv, Dh)
    return paged_attention(q, k_cache.reshape(split), v_cache.reshape(split),
                           block_tables, context_lens, scale=scale,
                           impl="jnp", layer=layer)


# -- rotary position embedding ------------------------------------------------
class RoPEParam(Params):
    base = field(float, default=10000.0)
    layout = field(str, default="bshd", enum=("bshd", "bhsd"))
    # global position of the first row — sequence-parallel shards and
    # autoregressive decode pass their offset, mirroring the flash
    # kernel's q_offset/k_offset contract
    offset = field(int, default=0)


@register_op("RoPE", aliases=("rope",))
class RoPEOp(OpDef):
    """Rotary position embedding (RoFormer; the long-context standard):
    rotates each head-dim pair (x_i, x_{i+D/2}) by pos * base^(-2i/D),
    making Q.K^T depend on relative position only.  Applied to Q and K
    after the head reshape — composes with FlashAttention in either
    layout, GQA (apply per tensor), and sequence shards via ``offset``.
    Elementwise cos/sin — XLA fuses it into the surrounding projections;
    no kernel needed.
    """

    param_cls = RoPEParam

    def list_arguments(self, params):
        return ["data"]

    def infer_shape(self, params, in_shapes):
        d = in_shapes[0]
        if d is None:
            raise ValueError("RoPE: data shape unknown")
        if d[-1] % 2:
            raise ValueError(f"RoPE: head_dim must be even, got {d[-1]}")
        return [tuple(d)], [tuple(d)], []

    def forward(self, params, inputs, aux, train, key):
        (x,) = inputs
        seq_axis = 1 if params.layout == "bshd" else 2
        S, D = x.shape[seq_axis], x.shape[-1]
        half = D // 2
        inv_freq = params.base ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        pos = jnp.arange(S, dtype=jnp.float32) + params.offset
        ang = pos[:, None] * inv_freq[None, :]          # (S, D/2)
        shape = [1] * x.ndim
        shape[seq_axis] = S
        shape[-1] = half
        cos = jnp.cos(ang).reshape(shape)
        sin = jnp.sin(ang).reshape(shape)
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin,
                               x1 * sin + x2 * cos], axis=-1)
        return [out.astype(x.dtype)], []
