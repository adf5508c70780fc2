#!/usr/bin/env python
"""Training-loop dispatch benchmark: fused single-dispatch train step
vs the classic per-parameter update loop.

Measures steps/sec and per-batch host dispatch count (compiled-program
calls, from the ``mxtpu_train_dispatches_total`` telemetry counter) for
the same model/data through both paths.  The CPU smoke config is small
enough that Python/dispatch overhead dominates — exactly the overhead
the fused path removes — so the speedup here is the *dispatch-bound*
bound; on TPU the win comes additionally from donation (in-place param
buffers) and uninterrupted device occupancy.

Emits the shared last-line-JSON + ``--json`` artifact contract
(complete:true stamped before the final record): TRAIN_BENCH.json.

Usage: python tools/train_bench.py [--backend cpu] [--json OUT]
"""

import argparse
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(mx, layers, hidden):
    data = mx.sym.Variable("data")
    net = data
    for i in range(layers):
        net = mx.sym.FullyConnected(net, name=f"fc{i}", num_hidden=hidden)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="out", num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def run_mode(mx, np, telemetry, args, fused):
    """Train fresh modules through one path; returns the measurement."""
    os.environ["MXTPU_FUSED_STEP"] = "1" if fused else "0"
    try:
        rng = np.random.RandomState(0)
        X = rng.randn(args.batches * args.batch, args.dim).astype(np.float32)
        y = rng.randint(0, 10, args.batches * args.batch).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=args.batch)
        net = build_model(mx, args.layers, args.hidden)
        mx.random.seed(0)
        mod = mx.mod.Module(net, context=mx.cpu() if args.platform != "tpu"
                            else mx.tpu())
        # warmup epoch compiles every program (fused: 1; unfused:
        # fwd_bwd + one kernel per optimizer); the timed fit reuses the
        # same bound executors and optimizer, so it measures pure
        # steady-state dispatch throughput
        mod.logger = logging.getLogger("train_bench.quiet")
        mod.logger.setLevel(logging.ERROR)  # already-bound warnings
        mod.fit(it, num_epoch=1, optimizer=args.optimizer,
                optimizer_params={"learning_rate": 0.01},
                initializer=mx.initializer.Xavier(), kvstore=None)

        # dispatch counts by snapshot DELTA, not telemetry.reset():
        # instrumented sites cache their counter children, and a
        # registry clear would detach the warmed-up module's handles
        # from future snapshots (metrics.Registry.clear contract)
        def dispatch_kinds():
            snap = telemetry.registry().snapshot().get(
                "mxtpu_train_dispatches_total", {"samples": []})
            return {s["labels"]["kind"]: s["value"] for s in snap["samples"]}

        before = dispatch_kinds()
        tic = time.perf_counter()
        mod.fit(it, num_epoch=args.epochs, optimizer=args.optimizer,
                optimizer_params={"learning_rate": 0.01},
                initializer=mx.initializer.Xavier(), kvstore=None)
        # fit's epoch-end get_params syncs the device, so the clock
        # covers completed work
        wall = time.perf_counter() - tic
        steps = args.epochs * args.batches
        kinds = {k: v - before.get(k, 0)
                 for k, v in dispatch_kinds().items()
                 if v - before.get(k, 0)}
        return {
            "mode": "fused" if fused else "per_param",
            "steps_per_sec": round(steps / wall, 2),
            "wall_s": round(wall, 3),
            "steps": steps,
            "dispatches_per_batch": round(sum(kinds.values()) / steps, 2),
            "dispatch_kinds": kinds,
        }
    finally:
        os.environ.pop("MXTPU_FUSED_STEP", None)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--batches", type=int, default=32,
                   help="batches per epoch")
    p.add_argument("--epochs", type=int, default=3,
                   help="timed epochs per mode")
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--json", default=None)
    p.add_argument("--backend", "--platform", dest="platform", default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import numpy as np

    import mxnet_tpu as mx

    import jax

    from mxnet_tpu import telemetry
    from tools.bench_io import make_flush

    telemetry.enable()
    args.platform = jax.default_backend()
    num_params = 2 * (args.layers + 1)  # weight+bias per FC
    out = {"platform": args.platform,
           "device_kind": getattr(jax.devices()[0], "device_kind", ""),
           "model": f"mlp{args.layers}x{args.hidden}",
           "num_params": num_params,
           "batch": args.batch, "batches_per_epoch": args.batches,
           "optimizer": args.optimizer}
    flush = make_flush(args.json, out)
    pts = []
    out["points"] = pts

    for fused in (False, True):
        rec = run_mode(mx, np, telemetry, args, fused)
        print(json.dumps(rec))
        pts.append(rec)
        flush(False)

    unfused, fused = pts[0], pts[1]
    out["unfused_steps_per_sec"] = unfused["steps_per_sec"]
    out["fused_steps_per_sec"] = fused["steps_per_sec"]
    out["speedup"] = round(fused["steps_per_sec"]
                           / unfused["steps_per_sec"], 2)
    out["unfused_dispatches_per_batch"] = unfused["dispatches_per_batch"]
    out["fused_dispatches_per_batch"] = fused["dispatches_per_batch"]
    out["telemetry"] = telemetry.snapshot()
    flush(True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
