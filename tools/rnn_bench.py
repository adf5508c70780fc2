#!/usr/bin/env python
"""Fused-RNN kernel benchmark: Pallas LSTM/GRU vs the lax.scan cell.

The reference's fused-RNN perf story is the cuDNN v5 kernel
(src/operator/cudnn_rnn-inl.h): one fused launch per layer instead of
per-step kernels.  The TPU analog (ops/pallas_lstm.py / pallas_gru.py)
keeps the recurrent weights and carried state resident in VMEM across
the whole time loop, cutting weight traffic from O(T*H^2) to O(H^2);
under a ``lax.scan`` the weights stream from HBM every step.  This tool
measures that claim: fwd+bwd wall time of the fused kernel vs the scan
cell at training shapes, with the timing loop ON DEVICE
(parallel/collectives._device_loop_s — a host loop's per-call dispatch
would swamp ms-scale kernels).

Usage: python tools/rnn_bench.py [--shapes T,N,H;...] [--json OUT]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def bench_one(jax, jnp, mode, T, N, H, n_iter=50):
    import numpy as np

    from mxnet_tpu.parallel.collectives import _device_loop_s

    if mode == "lstm":
        from mxnet_tpu.ops.pallas_lstm import fused_lstm as fused
        from mxnet_tpu.ops.pallas_lstm import fused_lstm_eligible as eligible
    else:
        from mxnet_tpu.ops.pallas_gru import fused_gru as fused
        from mxnet_tpu.ops.pallas_gru import fused_gru_eligible as eligible

    G = (4 if mode == "lstm" else 3) * H
    rng = np.random.RandomState(0)
    gx = jnp.asarray(rng.normal(0, 1, (T, N, G)).astype(np.float32))
    h0 = jnp.zeros((N, H), jnp.float32)
    c0 = jnp.zeros((N, H), jnp.float32)
    wh = jnp.asarray(rng.normal(0, 0.08, (G, H)).astype(np.float32))
    bh = jnp.asarray(rng.normal(0, 0.08, (G,)).astype(np.float32))

    def scan_fn(gx, h0, c0, wh, bh):
        if mode == "lstm":
            def cell(carry, g):
                h, c = carry
                acts = g + h @ wh.T + bh
                i, f, gg, o = jnp.split(acts, 4, axis=-1)
                c = (jax.nn.sigmoid(f) * c
                     + jax.nn.sigmoid(i) * jnp.tanh(gg))
                h = jax.nn.sigmoid(o) * jnp.tanh(c)
                return (h, c), h
            (hT, cT), ys = jax.lax.scan(cell, (h0, c0), gx)
        else:
            def cell(h, g):
                gr, gz, gn_x = jnp.split(g, 3, axis=-1)
                hr, hz, hn = jnp.split(h @ wh.T + bh, 3, axis=-1)
                r = jax.nn.sigmoid(gr + hr)
                z = jax.nn.sigmoid(gz + hz)
                n = jnp.tanh(gn_x + r * hn)
                h = (1 - z) * n + z * h
                return h, h
            hT, ys = jax.lax.scan(cell, h0, gx)
        return ys

    def fused_fn(gx, h0, c0, wh, bh):
        if mode == "lstm":
            ys, _, _ = fused(gx, h0, c0, wh, bh)
        else:
            ys, _ = fused(gx, h0, wh, bh)
        return ys

    def timed(fn):
        loss = lambda gx_, wh_: jnp.sum(fn(gx_, h0, c0, wh_, bh) ** 2)
        grad_fn = jax.grad(loss, argnums=(0, 1))
        eps = jnp.float32(1e-8)

        def step(carry):
            gx_c, wh_c = carry
            dgx, dwh = grad_fn(gx_c, wh_c)
            return (gx + dgx * eps, wh + dwh * eps)

        return _device_loop_s(step, (gx, wh), n_iter)

    rec = {"mode": mode, "seq_len": T, "batch": N, "hidden": H,
           "eligible": bool(eligible(T, N, H))}
    try:
        rec["scan_ms"] = round(timed(scan_fn) * 1e3, 3)
    except Exception as e:
        rec["scan_error"] = type(e).__name__
    try:
        rec["fused_ms"] = round(timed(fused_fn) * 1e3, 3)
    except Exception as e:
        rec["fused_error"] = type(e).__name__
    if rec.get("scan_ms") and rec.get("fused_ms"):
        rec["speedup"] = round(rec["scan_ms"] / rec["fused_ms"], 2)
    # the VMEM-residency model: scan re-reads G*H recurrent weights every
    # step; fused reads them once
    rec["scan_weight_traffic_mb"] = round(T * G * H * 4 / 1e6, 1)
    rec["fused_weight_traffic_mb"] = round(G * H * 4 / 1e6, 1)
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--shapes", default="128,8,512;128,8,256;32,8,128",
                   help="semicolon-separated T,N,H triples")
    p.add_argument("--json", default=None,
                   help="append results as one JSON line to this file")
    p.add_argument("--platform", default=None)
    p.add_argument("--n-iter", type=int, default=50)
    args = p.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    points = []
    out = {"platform": jax.default_backend(),
           "device_kind": getattr(jax.devices()[0], "device_kind", ""),
           "points": points}

    from tools.bench_io import make_flush

    flush = make_flush(args.json, out)

    for trip in args.shapes.split(";"):
        T, N, H = (int(x) for x in trip.split(","))
        for mode in ("lstm", "gru"):
            rec = bench_one(jax, jnp, mode, T, N, H, n_iter=args.n_iter)
            print(json.dumps(rec))
            points.append(rec)
            flush(False)
    flush(True)


if __name__ == "__main__":
    main()
