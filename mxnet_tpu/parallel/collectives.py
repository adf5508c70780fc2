"""Collective primitives + the ICI all-reduce bandwidth benchmark.

Replacement for the reference's §2.4 communication column (CommCPU tree
reduce, CommDevice P2P all-reduce, ps-lite ZPush/ZPull): on TPU these are
XLA collectives (psum / all_gather / reduce_scatter / ppermute) issued
inside compiled programs over the mesh.  ``allreduce_bench`` is the port
of tools/bandwidth/measure.py — the harness behind BASELINE.md's
"KVStore all-reduce GB/s per device" metric.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["psum", "all_gather", "reduce_scatter", "ppermute", "allreduce",
           "allreduce_bench"]

# re-exported lax collectives (usable inside shard_map'd functions)
psum = jax.lax.psum
all_gather = jax.lax.all_gather
ppermute = jax.lax.ppermute


def reduce_scatter(x, axis_name):
    return jax.lax.psum_scatter(x, axis_name, tiled=True)


def allreduce(arrays, mesh: Mesh, axis_name="dp"):
    """All-reduce a pytree of per-device-sharded arrays over one mesh axis.

    Equivalent of KVStore push+pull fused: each leaf is stacked on a
    leading device axis; result is the sum, replicated.
    """
    spec = PartitionSpec(axis_name)

    @jax.jit
    def _ar(xs):
        def inner(*leaves):
            return tuple(jax.lax.psum(l, axis_name) for l in leaves)

        flat, treedef = jax.tree_util.tree_flatten(xs)
        out = jax.shard_map(inner, mesh=mesh,
                            in_specs=(spec,) * len(flat),
                            out_specs=(spec,) * len(flat))(*flat)
        return jax.tree_util.tree_unflatten(treedef, out)

    return _ar(arrays)


def _device_loop_s(step, x0, n_iter):
    """Per-iteration seconds of ``step`` with the loop ON DEVICE.

    Every host-side call pays a dispatch that can dwarf ms-scale
    device work, so a host loop measures dispatch, not compute.
    ``fori_loop`` with a TRACED trip count compiles once and serializes
    iterations through the carried value; the slope between two trip
    counts cancels the constant per-call overhead."""
    run_n = jax.jit(lambda n: jax.lax.fori_loop(0, n, lambda i, c: step(c),
                                                x0))
    jax.block_until_ready(run_n(1))           # compile + warm
    n_lo, n_hi = 2, 2 + n_iter
    tic = time.perf_counter()
    jax.block_until_ready(run_n(n_lo))
    t_lo = time.perf_counter() - tic
    tic = time.perf_counter()
    jax.block_until_ready(run_n(n_hi))
    t_hi = time.perf_counter() - tic
    return max((t_hi - t_lo) / (n_hi - n_lo), 1e-9)


def allreduce_bench(mesh=None, sizes_mb=(1, 4, 16, 64, 256), n_iter=10,
                    dtype=jnp.float32, verbose=True):
    """Measure all-reduce algorithmic bandwidth per device over the mesh.

    Port of tools/bandwidth/measure.py: reports GB/s/device using the
    2(n-1)/n ring all-reduce traffic model on the gradient-sized buffers.
    """
    if mesh is None:
        from .mesh import local_mesh

        mesh = local_mesh("dp")
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    results = []
    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 / np.dtype(dtype).itemsize)
        sharding = NamedSharding(mesh, PartitionSpec(axis))
        x = jax.device_put(
            jnp.ones((n, elems), dtype), sharding)

        step = lambda v: jax.shard_map(
            lambda t: jax.lax.psum(t, axis), mesh=mesh,
            in_specs=PartitionSpec(axis),
            out_specs=PartitionSpec(axis))(v)
        dt = _device_loop_s(step, x, n_iter)
        bytes_moved = 2 * (n - 1) / max(n, 1) * elems * np.dtype(dtype).itemsize
        gbps = bytes_moved / dt / 1e9
        results.append({"size_mb": mb, "time_s": dt, "gbps_per_device": gbps})
        if verbose:
            print(f"allreduce {mb:7.2f} MB over {n} devices: {dt*1e3:8.2f} ms, "
                  f"{gbps:7.2f} GB/s/device")
    return results


def memory_bench(sizes_mb=(64, 256, 1024), n_iter=10, dtype=jnp.float32,
                 verbose=True):
    """Single-device memory-system bandwidth: HBM stream (read+write an
    elementwise op) and host<->device staging transfers.

    The single-chip complement of :func:`allreduce_bench` for the
    bandwidth artifact (reference tools/bandwidth measures PCIe paths the
    same way); on TPU the HBM number should sit near the chip's spec
    (e.g. ~2.7 TB/s on v5p) and staging near PCIe speeds.
    """
    dev = jax.devices()[0]
    results = []
    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 / np.dtype(dtype).itemsize)
        x = jax.device_put(jnp.ones((elems,), dtype), dev)
        dt = _device_loop_s(lambda v: v + 1, x, n_iter)
        hbm_gbps = 2 * elems * np.dtype(dtype).itemsize / dt / 1e9

        host = np.ones((elems,), np.dtype(dtype))
        tic = time.perf_counter()
        for _ in range(n_iter):
            jax.device_put(host, dev).block_until_ready()
        h2d = elems * host.itemsize * n_iter / (time.perf_counter() - tic) / 1e9
        # On accelerators device_get already materializes host memory; the
        # extra np.array copy is only needed on the CPU backend, where
        # device_get is a zero-copy view that would time as infinite.
        force_copy = dev.platform == "cpu"
        tic = time.perf_counter()
        for _ in range(n_iter):
            out = jax.device_get(x)
            if force_copy:
                np.array(out)
        d2h = elems * host.itemsize * n_iter / (time.perf_counter() - tic) / 1e9
        results.append({"size_mb": mb, "hbm_gbps": hbm_gbps,
                        "h2d_gbps": h2d, "d2h_gbps": d2h})
        if verbose:
            print(f"memory {mb:7.2f} MB: HBM {hbm_gbps:8.1f} GB/s, "
                  f"h2d {h2d:6.2f} GB/s, d2h {d2h:6.2f} GB/s")
    return results
