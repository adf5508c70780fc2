"""Ulysses-style all-to-all sequence parallelism.

New first-class TPU capability (absent in the reference — SURVEY.md §2.4
marks sequence parallelism "No").  Complement to ring attention
(``parallel/ring_attention.py``): instead of rotating K/V shards around
the ring, two ``all_to_all`` collectives re-shard the activations from
sequence-parallel to head-parallel layout and back:

    (B, H, S/n, D)  --all_to_all-->  (B, H/n, S, D)
         attention over the FULL sequence per local head group
    (B, H/n, S, D)  --all_to_all-->  (B, H, S/n, D)

Each chip then runs an ordinary (flash) attention over its head subset,
so the attention inner loop needs no per-step communication — the
tradeoff vs the ring is 2 all-to-alls of activation size against n
ppermutes of K/V size, and the head count must divide the mesh axis.

API mirrors ``ring_attention``: ``ulysses_attention(q, k, v, mesh,
axis, causal, impl, layout)`` with q/k/v (batch, heads, seq, head_dim)
for ``layout="bhsd"`` or sequence-major (batch, seq, heads, head_dim)
for ``layout="bshd"`` (the all-to-alls split/concat the same two axes
in either order, so BSHD stays transpose-free end to end), sharded
over ``axis`` on the sequence dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

__all__ = ["ulysses_attention"]


def _dense_attention(q, k, v, scale, causal, window=0):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal or window:
        # the kernel's band-mask helper is the single source of the
        # causal/window semantics
        from ..ops.flash_attention import _mask_for

        S = q.shape[2]
        s = jnp.where(_mask_for(0, 0, S, S, causal, 0, 0, window),
                      s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@functools.lru_cache(maxsize=64)
def _build_ulysses_run(mesh: Mesh, axis: str, scale: float, causal: bool,
                       impl: str, block_q: int, block_k: int,
                       interpret: bool, layout: str = "bhsd",
                       batch_axis=None, window=0):
    """Cached compiled program per (mesh, axis, config) — same caching
    contract as ring_attention's _build_ring_run."""
    from .ring_attention import _ring_spec

    bshd = layout == "bshd"
    spec = _ring_spec(layout, axis, batch_axis)
    # the all-to-all trades the sharded axis for the head axis; both
    # layouts keep their own order end to end (bshd: seq=1, heads=2)
    seq_ax, head_ax = (1, 2) if bshd else (2, 1)

    @jax.jit
    def run(q, k, v):
        def shard_fn(q_s, k_s, v_s):
            # seq-sharded -> head-sharded: split heads, gather sequence
            def to_heads(x):
                return lax.all_to_all(x, axis, split_axis=head_ax,
                                      concat_axis=seq_ax, tiled=True)

            qh, kh, vh = to_heads(q_s), to_heads(k_s), to_heads(v_s)
            if kh.shape[head_ax] != qh.shape[head_ax] and impl != "flash":
                # native-GQA shards reach the dense body with fewer kv
                # heads per group; the kernel groups natively but the
                # einsum needs equal head counts — expand per shard
                rep = qh.shape[head_ax] // kh.shape[head_ax]
                kh = jnp.repeat(kh, rep, axis=head_ax)
                vh = jnp.repeat(vh, rep, axis=head_ax)
            # window passes straight through: after the all-to-all
            # each head group holds the FULL sequence, so the band
            # mask is the ordinary local one
            if impl == "flash":
                from ..ops.flash_attention import flash_attention

                oh = flash_attention(qh, kh, vh, causal=causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret, layout=layout,
                                     window=window)
            elif bshd:
                oh = _dense_attention(qh.transpose(0, 2, 1, 3),
                                      kh.transpose(0, 2, 1, 3),
                                      vh.transpose(0, 2, 1, 3),
                                      scale, causal,
                                      window).transpose(0, 2, 1, 3)
            else:
                oh = _dense_attention(qh, kh, vh, scale, causal, window)
            # head-sharded -> seq-sharded: split sequence, gather heads
            return lax.all_to_all(oh, axis, split_axis=seq_ax,
                                  concat_axis=head_ax, tiled=True)

        return jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return run


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal=False,
                      impl="auto", block_q=512, block_k=512, layout="bhsd",
                      batch_axis=None, window=0):
    """All-to-all sequence-parallel multi-head attention.

    q/k/v: (batch, heads, seq, head_dim) for ``layout="bhsd"`` or
    (batch, seq, heads, head_dim) for ``layout="bshd"`` (sequence-major
    — the all-to-alls and the kernel preserve the order, so no
    activation transposes), sharded over ``axis`` on the sequence
    dimension (replicated arrays are accepted and sharded here).
    Requires heads %% mesh.shape[axis] == 0.  Returns the attention
    output with the same layout and sequence sharding.

    impl: "flash" = fused Pallas kernel per head group; "xla" = dense
    softmax attention; "auto" picks flash on TPU when shapes fit.
    """
    from ..ops import pallas_util
    from .ring_attention import _flash_available, _ring_spec

    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"layout must be 'bhsd' or 'bshd', got {layout!r}")
    head_axis, seq_axis = (2, 1) if layout == "bshd" else (1, 2)
    n_shards = mesh.shape[axis]
    H = q.shape[head_axis]
    if k.shape[head_axis] != H:
        # grouped-query k/v: the all-to-alls re-shard the HEAD axis.
        # When the kv heads ALSO divide the mesh axis the K/V
        # all-to-alls simply split the reduced axis — GQA stays native
        # (each head group attends with Hkv/sp shared K/V heads in the
        # kernel).  Otherwise expand to full heads first.
        from ..ops.flash_attention import gqa_group
        rep = gqa_group(H, k.shape[head_axis])
        if k.shape[head_axis] % n_shards:
            k = jnp.repeat(k, rep, axis=head_axis)
            v = jnp.repeat(v, rep, axis=head_axis)
    if H % n_shards != 0:
        raise ValueError(
            f"ulysses_attention: heads ({H}) must be divisible by the "
            f"'{axis}' mesh axis ({n_shards}); use ring_attention for "
            "head counts that do not divide the mesh")
    if window < 0:
        raise ValueError(f"ulysses_attention: window must be >= 0 "
                         f"(got {window})")
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    S = q.shape[seq_axis]
    interpret = not pallas_util.on_tpu()
    if impl == "auto":
        from ..ops.flash_attention import flash_eligible
        fits = flash_eligible(S, S, block_q, block_k)
        impl = ("flash" if (not interpret and fits
                            and _flash_available(layout))
                else "xla")
    run = _build_ulysses_run(mesh, axis, scale, bool(causal), impl,
                             block_q, block_k, interpret, layout,
                             batch_axis, int(window))

    if not isinstance(q, jax.core.Tracer):
        sharding = NamedSharding(mesh, _ring_spec(layout, axis, batch_axis))
        q = jax.device_put(q, sharding)
        k = jax.device_put(k, sharding)
        v = jax.device_put(v, sharding)
    return run(q, k, v)
