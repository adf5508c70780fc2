"""Ring attention: sequence/context parallelism for long sequences.

New first-class TPU capability (absent in the reference — SURVEY.md §2.4
marks sequence parallelism "No"; its long-sequence story was bucketing +
fused RNN).  Implements blockwise ring attention (Liu et al.: each chip
holds one sequence shard of Q/K/V; K/V shards rotate around the ring via
``ppermute`` over ICI while each chip accumulates its Q-block's attention
with streaming log-sum-exp renormalization).  Peak memory per chip is
O(S/n * S/n) instead of O(S^2); communication fully overlaps compute on
the ring.

Exposed as ``ring_attention(q, k, v, mesh, axis)`` — a jitted sharded
call (the single-device symbol-graph entry is ``mx.sym.FlashAttention``,
ops/attention.py; ``parallel/ulysses.py`` is the all-to-all variant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ring_attention", "attention_reference"]


def _block_attn(q, k, v, scale, causal_mask=None):
    """Scores for one (Q-block, K-block) pair with running-max stats.

    Returns (unnormalized out, row max, row sumexp)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal_mask is not None:
        s = jnp.where(causal_mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    # fully-masked rows have m = -inf; subtract a finite stand-in so
    # exp(-inf - m_safe) = 0 instead of NaN
    m_safe = jnp.maximum(m, -1e30)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, l


def _ring_body(q, k, v, axis_name, n_shards, scale, causal, q_index,
               window=0, n_steps=None):
    """Per-shard ring loop: rotate K/V, accumulate with LSE renorm."""
    B, H, S_blk, D = q.shape
    if k.shape[1] != H:
        # grouped-query k/v through the dense fallback: expand here.
        # (The flash body passes reduced K/V to the kernel, which
        # groups natively under bshd; under bhsd the kernel expands
        # internally per step — still reduced traffic on the ring's
        # ppermutes either way.)
        from ..ops.flash_attention import gqa_group
        rep = gqa_group(H, k.shape[1])
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    def step(carry, i):
        k_cur, v_cur, o_acc, m_acc, l_acc = carry
        if causal or window:
            # the kernel's own global-position band mask (ONE source of
            # the causal/window semantics — plain jnp, works outside
            # pallas too), with the current K/V shard's offset
            from ..ops.flash_attention import _mask_for

            kv_index = (q_index - i) % n_shards
            mask = _mask_for(0, 0, S_blk, S_blk, causal,
                             q_index * S_blk, kv_index * S_blk, window)
            mask = jnp.broadcast_to(mask, (B, H, S_blk, S_blk))
        else:
            mask = None
        o_blk, m_blk, l_blk = _block_attn(q, k_cur, v_cur, scale, mask)
        # streaming renormalization
        m_new = jnp.maximum(m_acc, m_blk)
        # guard -inf blocks (fully masked): exp(-inf - -inf) -> use where
        c_acc = jnp.where(jnp.isfinite(m_acc), jnp.exp(m_acc - m_new), 0.0)
        c_blk = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - m_new), 0.0)
        o_new = o_acc * c_acc[..., None] + o_blk * c_blk[..., None]
        l_new = l_acc * c_acc + l_blk * c_blk
        # rotate K/V around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, o_new, m_new, l_new), None

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((B, H, S_blk), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, S_blk), q.dtype)
    (k, v, o, m, l), _ = lax.scan(step, (k, v, o0, m0, l0),
                                  jnp.arange(n_steps or n_shards))
    return o / jnp.maximum(l, 1e-20)[..., None]


def _ring_body_flash(q, k, v, axis_name, n_shards, scale, causal, q_index,
                     block_q, block_k, interpret, layout="bhsd", window=0,
                     n_steps=None):
    """Ring loop where each shard-pair attention block is the fused
    Pallas flash kernel (ops/flash_attention.py); per-step normalized
    outputs are stream-combined via their log-sum-exps.  The kernel's
    causal mask uses global positions = shard_index * S_blk + local, so
    diagonal / past / future K-V shards all fall out of one kernel.

    ``layout="bshd"`` keeps shards sequence-major end to end (the
    kernel indexes the head dim; the only reshuffle is the tiny
    D-free log-sum-exp row map)."""
    from ..ops.flash_attention import flash_attention

    bshd = layout == "bshd"
    if bshd:
        B, S_blk, H, D = q.shape
        row0 = (B, S_blk, H)
    else:
        B, H, S_blk, D = q.shape
        row0 = (B, H, S_blk)

    def step(carry, i):
        k_cur, v_cur, o_acc, m_acc, l_acc = carry
        kv_index = (q_index - i) % n_shards
        o_b, lse_b = flash_attention(
            q, k_cur, v_cur, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k,
            q_offset=q_index * S_blk, k_offset=kv_index * S_blk,
            return_lse=True, interpret=interpret, layout=layout,
            window=window)
        if bshd:
            # lse is (B, H, S); the output rows are (B, S, H)
            lse_b = jnp.moveaxis(lse_b, 1, 2)
        # streaming logsumexp-weighted combine of normalized outputs;
        # accumulate in float32 regardless of input dtype (bf16 inputs
        # would otherwise promote the scan carry and break its type)
        m_new = jnp.maximum(m_acc, lse_b)
        c_acc = jnp.where(jnp.isfinite(m_acc), jnp.exp(m_acc - m_new), 0.0)
        c_b = jnp.exp(lse_b - m_new)
        o_new = o_acc * c_acc[..., None] + \
            o_b.astype(jnp.float32) * c_b[..., None]
        l_new = l_acc * c_acc + c_b
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, o_new, m_new, l_new), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(row0, -jnp.inf, jnp.float32)
    l0 = jnp.zeros(row0, jnp.float32)
    (k, v, o, m, l), _ = lax.scan(step, (k, v, o0, m0, l0),
                                  jnp.arange(n_steps or n_shards))
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


@functools.lru_cache(maxsize=64)
def _build_ring_run(mesh: Mesh, axis: str, scale: float, causal: bool,
                    impl: str, block_q: int, block_k: int, interpret: bool,
                    layout: str = "bhsd", batch_axis=None, window=0,
                    n_steps=None):
    """Cached compiled ring-attention program per (mesh, axis, config) —
    jax.jit caches on function identity, so the shard_map must be built
    once per config or every call recompiles."""
    n_shards = mesh.shape[axis]
    bshd = layout == "bshd"
    spec = _ring_spec(layout, axis, batch_axis)

    @jax.jit
    def run(q, k, v):
        def shard_fn(q_s, k_s, v_s):
            idx = lax.axis_index(axis)
            if impl == "flash":
                return _ring_body_flash(q_s, k_s, v_s, axis, n_shards, scale,
                                        causal, idx, block_q, block_k,
                                        interpret, layout=layout,
                                        window=window, n_steps=n_steps)
            if bshd:
                # dense fallback computes in BHSD; transpose at the
                # shard boundary (correctness path, not the TPU path)
                o = _ring_body(q_s.transpose(0, 2, 1, 3),
                               k_s.transpose(0, 2, 1, 3),
                               v_s.transpose(0, 2, 1, 3),
                               axis, n_shards, scale, causal, idx,
                               window=window, n_steps=n_steps)
                return o.transpose(0, 2, 1, 3)
            return _ring_body(q_s, k_s, v_s, axis, n_shards, scale, causal,
                              idx, window=window, n_steps=n_steps)

        return jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return run


def _ring_spec(layout, axis, batch_axis=None):
    """The one seq-sharded PartitionSpec both the shard_map and the
    caller-side device_put use — they must never desync.  With
    ``batch_axis`` the batch dim is additionally dp-sharded (combined
    dp x sp mesh: each dp replica's sp group runs its own ring — the
    ppermutes stay inside the sp axis)."""
    if layout == "bshd":
        return PartitionSpec(batch_axis, axis, None, None)
    return PartitionSpec(batch_axis, None, axis, None)


_FLASH_AVAILABLE = {}


def _flash_available(layout="bhsd"):
    """One-time probe PER LAYOUT: compile+run the Pallas kernel on a
    tiny shape so 'auto' can fall back to the XLA body if Mosaic
    lowering fails on this backend/driver combo rather than erroring
    mid-training.  The bhsd (flattened 3D) and bshd (4D head-indexed
    BlockSpec) lowerings are distinct programs, so each layout is
    probed separately."""
    if layout not in _FLASH_AVAILABLE:
        try:
            from ..ops.flash_attention import flash_attention

            # ensure_compile_time_eval: ring_attention is routinely
            # called inside a jitted train step, where a plain probe
            # would be staged into the outer trace (never actually
            # compiled/run here) and block_until_ready on the tracer
            # would no-op — caching True without exercising Mosaic.
            # head_dim 128 matches the MXU lane layout real models use.
            with jax.ensure_compile_time_eval():
                shape = ((1, 128, 1, 128) if layout == "bshd"
                         else (1, 1, 128, 128))   # S=128, H=1 either way
                x = jnp.zeros(shape, jnp.float32)
                jax.block_until_ready(
                    flash_attention(x, x, x, layout=layout))
            _FLASH_AVAILABLE[layout] = True
        except Exception:
            _FLASH_AVAILABLE[layout] = False
    return _FLASH_AVAILABLE[layout]


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal=False,
                   impl="auto", block_q=512, block_k=512, layout="bhsd",
                   batch_axis=None, window=0):
    """Sharded multi-head attention over a sequence-parallel mesh axis.

    q/k/v: (batch, heads, seq, head_dim) for ``layout="bhsd"`` or
    (batch, seq, heads, head_dim) for ``layout="bshd"`` (sequence-major
    — shards feed the flash kernel with zero activation transposes),
    sharded over ``axis`` on the seq dimension (replicated arrays are
    accepted and sharded here).  Returns the attention output with the
    same layout and sharding.

    impl: "flash" runs each shard-pair block through the fused Pallas
    kernel; "xla" uses the jnp blockwise body; "auto" picks flash on
    TPU (when the shard length divides the kernel block sizes) and xla
    elsewhere.  K/V may carry fewer heads than q (grouped-query
    attention): the flash body streams the reduced K/V shards around
    the ring natively — the GQA traffic saving applies to the ring
    ppermutes too — and the dense body expands.

    batch_axis: optional dp mesh axis the batch dim is ALSO sharded
    over (combined dp x sp data+sequence parallelism); each dp
    replica's sp group runs an independent ring.
    """
    from ..ops import pallas_util

    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout must be 'bhsd' or 'bshd', got {layout!r}")
    seq_axis = 1 if layout == "bshd" else 2
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    n_shards = mesh.shape[axis]
    S_blk = q.shape[seq_axis] // n_shards
    interpret = not pallas_util.on_tpu()
    if impl == "auto":
        from ..ops.flash_attention import flash_eligible
        fits = flash_eligible(S_blk, S_blk, block_q, block_k)
        impl = ("flash" if (not interpret and fits
                            and _flash_available(layout))
                else "xla")
    if window < 0:
        raise ValueError(f"ring_attention: window must be >= 0 "
                         f"(got {window})")
    n_steps = None
    if window and causal:
        # sliding-window + causal bounds the ring: a K/V shard i steps
        # back is entirely below the band once (i-1)*S_blk + 1 >= window
        # (min q-k distance between the shards), so only the diagonal
        # and ceil((window-1)/S_blk) predecessors can contribute — at
        # long S with small windows the ring shrinks to neighbor
        # exchanges (the point of windowed attention over shards)
        import math
        n_steps = min(n_shards, 1 + math.ceil((window - 1) / S_blk))
    run = _build_ring_run(mesh, axis, scale, bool(causal), impl,
                          block_q, block_k, interpret, layout, batch_axis,
                          int(window), n_steps)

    if not isinstance(q, jax.core.Tracer):
        sharding = NamedSharding(mesh, _ring_spec(layout, axis, batch_axis))
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
    return run(q, k, v)


def attention_reference(q, k, v, causal=False):
    """Dense single-device attention for testing."""
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S = q.shape[2]
        mask = np.tril(np.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
