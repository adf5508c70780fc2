#!/usr/bin/env python
"""Long-context attention benchmark: tokens/sec + peak HBM vs sequence.

Two lanes (SURVEY §5 long-context bar; VERDICT r4 item 8):

  single  flash vs dense XLA attention fwd+bwd at S=8k/16k/32k on the
          local default backend — tokens/sec and the compiled peak-HBM
          estimate per path.  The dense (S x S) score tensor leaves
          HBM entirely around S=16k on a 16GB chip (that OOM is data:
          flash's raison d'etre at long context).
  ring    ring_attention over an sp mesh at fixed GLOBAL sequence,
          sweeping the sp axis width — the sequence-parallel scaling
          shape.  On a single-chip host this runs on a virtual CPU
          mesh (platform: cpu, noted in the record); the TPU follow-up
          is the same command on a real multi-chip slice.

Usage: python tools/longcontext_bench.py [--lane single|ring|both]
           [--seqs 8192,16384,32768] [--json OUT]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _peak_hbm_bytes(jitted, *args):
    """Compiled peak-HBM estimate (arguments + outputs + XLA temps) —
    the honest 'does this sequence length fit' number, available
    without running a step."""
    try:
        mem = jitted.lower(*args).compile().memory_analysis()
        return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes)
    except Exception:
        return None


def bench_single(jax, jnp, S, B, H, D, n_iter=30):
    """flash vs dense fwd+bwd at one sequence length (causal)."""
    import numpy as np

    from mxnet_tpu.ops.flash_attention import flash_attention
    from mxnet_tpu.parallel.collectives import _device_loop_s

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D), dt) for _ in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def loss_dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / np.sqrt(D))
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, jnp.asarray(-jnp.inf, s.dtype))
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v)
                       .astype(jnp.float32))

    rec = {"seq_len": S, "batch": B, "heads": H, "head_dim": D,
           "causal": True}
    for name, fn in (("flash", loss_flash), ("dense", loss_dense)):
        grad_fn = jax.grad(fn, argnums=(0, 1, 2))
        eps = jnp.asarray(1e-6, dt)

        def step(carry):
            qc, kc, vc = carry
            dq, dk, dv = grad_fn(qc, kc, vc)
            return (q + dq.astype(dt) * eps, k + dk.astype(dt) * eps,
                    v + dv.astype(dt) * eps)

        hbm = _peak_hbm_bytes(jax.jit(grad_fn), q, k, v)
        if hbm is not None:
            rec[name + "_peak_hbm_gb"] = round(hbm / 1e9, 3)
        try:
            # device-side fori-loop slope: no per-call host dispatch
            # in the timed window
            sec = _device_loop_s(step, (q, k, v), n_iter)
            rec[name + "_ms"] = round(sec * 1e3, 3)
            rec[name + "_tokens_per_sec"] = round(B * S / sec, 1)
        except Exception as e:   # dense OOM at long S IS the data point
            rec[name + "_error"] = type(e).__name__
    if rec.get("flash_ms") and rec.get("dense_ms"):
        rec["speedup"] = round(rec["dense_ms"] / rec["flash_ms"], 2)
    return rec


def bench_ring(jax, jnp, S_global, B, H, D, widths, n_iter=5):
    """ring_attention at fixed global S over an sp axis of each width —
    per-step time shape as sequence parallelism spreads the O(S^2)
    work (each device computes S_global * S_global/width scores)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.collectives import _device_loop_s
    from mxnet_tpu.parallel.ring_attention import ring_attention

    rng = np.random.RandomState(1)
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    q, k, v = (jnp.asarray(rng.randn(B, H, S_global, D), dt)
               for _ in range(3))
    points = []
    n_dev = len(jax.devices())
    for w in widths:
        if w > n_dev or S_global % w:
            continue
        mesh = mx.parallel.make_mesh({"sp": w})

        def attn(q, k, v):
            return jnp.sum(
                ring_attention(q, k, v, mesh, axis="sp", causal=True)
                .astype(jnp.float32))

        grad_fn = jax.grad(attn, argnums=(0, 1, 2))
        eps = jnp.asarray(1e-6, dt)

        def step(carry):
            qc, kc, vc = carry
            dq, dk, dv = grad_fn(qc, kc, vc)
            return (q + dq.astype(dt) * eps, k + dk.astype(dt) * eps,
                    v + dv.astype(dt) * eps)

        rec = {"sp": w, "seq_global": S_global, "seq_per_device":
               S_global // w}
        try:
            sec = _device_loop_s(step, (q, k, v), n_iter)
            rec["step_ms"] = round(sec * 1e3, 3)
            rec["tokens_per_sec"] = round(B * S_global / sec, 1)
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        points.append(rec)
    return points


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--lane", default="both",
                   choices=("single", "ring", "both"))
    p.add_argument("--seqs", default="8192,16384,32768")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--ring-seq", type=int, default=None,
                   help="global S for the ring lane (default: first "
                        "--seqs on tpu, 4096 on cpu)")
    p.add_argument("--ring-widths", default="1,2,4,8")
    p.add_argument("--json", default=None)
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    if (jax.default_backend() != "tpu" and len(jax.devices()) < 2
            and not os.environ.get("_MXTPU_LCB_REEXEC")):
        # ring lane needs a mesh: re-exec ONCE with a virtual CPU mesh
        os.environ["_MXTPU_LCB_REEXEC"] = "1"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
        os.execv(sys.executable, [sys.executable] + sys.argv
                 + ["--platform", "cpu"])
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"
    out = {"platform": jax.default_backend(),
           "device_kind": getattr(jax.devices()[0], "device_kind", ""),
           "n_devices": len(jax.devices())}

    from tools.bench_io import make_flush

    flush = make_flush(args.json, out)

    if args.lane in ("single", "both"):
        pts = []
        out["points"] = pts
        for S in (int(x) for x in args.seqs.split(",")):
            if not on_tpu and S > 8192:
                continue                 # CPU smoke: keep it tractable
            rec = bench_single(jax, jnp, S, args.batch, args.heads,
                               args.head_dim,
                               n_iter=30 if on_tpu else 3)
            print(json.dumps(rec))
            pts.append(rec)
            flush(False)
    if args.lane in ("ring", "both"):
        S_ring = args.ring_seq or (int(args.seqs.split(",")[0])
                                   if on_tpu else 4096)
        widths = [int(x) for x in args.ring_widths.split(",")]
        ring_pts = bench_ring(jax, jnp, S_ring, args.batch,
                              2 if not on_tpu else args.heads,
                              32 if not on_tpu else args.head_dim,
                              widths, n_iter=10 if on_tpu else 2)
        for rec in ring_pts:
            print(json.dumps(rec))
        out["ring"] = {"points": ring_pts,
                       "note": None if on_tpu else
                       "cpu virtual mesh: scaling SHAPE only; rerun on "
                       "a multi-chip slice for absolute numbers"}
    # stamp completion BEFORE the stdout record (same contract as
    # decode_bench: the last stdout line must carry "complete": true
    # on a finished run)
    flush(True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
