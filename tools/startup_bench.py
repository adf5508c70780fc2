#!/usr/bin/env python
"""Cold-vs-warm startup benchmark for the AOT subsystem
(mxnet_tpu/aot/): how long until a serve engine is ready to admit
traffic, restarting with and without persisted compile artifacts.

Two child processes measure the same engine config against the same
artifact directories:

  cold   empty JAX_COMPILATION_CACHE_DIR + MXTPU_AOT_DIR: every bucket
         program is traced, lowered, XLA-compiled — and written through
         to both stores on the way.
  warm   the directories the cold child just populated: programs
         deserialize from the export store (no Python re-trace) and
         their XLA compiles hit the persistent cache (disk reads).

Both children warm the full bucket grid (``Engine.warmup()``), so the
two ready-times cover an identical program set, then serve a small
deterministic workload whose token stream is hashed — the warm path
must be byte-identical, not just fast.  Compile activity is taken from
telemetry: ``mxtpu_aot_programs_total{source=trace}`` (fresh traces —
0 on a healthy warm start) and the ``mxtpu_compile_cache_*`` counters.

Emits the shared last-line-JSON + ``--json`` artifact contract
(complete:true stamped before the final record): STARTUP_BENCH.json.

Usage: python tools/startup_bench.py [--backend cpu] [--json OUT]
       [--keep-dirs DIR]
"""

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def child(args):
    """One measured engine start; prints a single JSON line."""
    import numpy as np

    import mxnet_tpu as mx

    import jax

    from mxnet_tpu import telemetry

    telemetry.enable()

    def counter(name, **labels):
        snap = telemetry.registry().snapshot().get(name, {"samples": []})
        return sum(s["value"] for s in snap["samples"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    S = args.max_model_len
    net = mx.models.gpt(args.vocab, S, num_layers=args.layers,
                        d_model=args.d_model, num_heads=args.heads)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(3)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 0.35 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale
                        + (1.0 if name.endswith("gamma") else 0.0)
                        ).astype(np.float32)

    # engine-ready time: construction + full-grid warmup (imports and
    # checkpoint synthesis above are deliberately outside the clock —
    # they cost the same either way)
    tic = time.perf_counter()
    eng = mx.serve.Engine(params, symbol=net, block_size=args.block_size,
                          num_blocks=args.num_blocks,
                          max_batch=args.max_batch, max_model_len=S,
                          max_prefills_per_step=2)
    programs = eng.warmup()
    ready_s = time.perf_counter() - tic

    prompts = [rng.randint(0, args.vocab, (n,)).astype(np.int32)
               for n in (7, 13, 5, 21)]
    reqs = [eng.submit(p, max_new_tokens=args.max_new) for p in prompts]
    tic = time.perf_counter()
    eng.run()
    serve_s = time.perf_counter() - tic
    toks = [r.tokens for r in reqs]
    n_tokens = sum(len(t) for t in toks)
    print(json.dumps({
        "platform": jax.default_backend(),
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "ready_s": round(ready_s, 3),
        "warmup_programs": programs,
        "fresh_traces": counter("mxtpu_aot_programs_total",
                                source="trace"),
        "artifact_loads": counter("mxtpu_aot_programs_total",
                                  source="artifact"),
        "cache_hits": counter("mxtpu_compile_cache_hits"),
        "cache_misses": counter("mxtpu_compile_cache_misses"),
        "cache_puts": counter("mxtpu_compile_cache_puts"),
        "tokens_per_sec": round(n_tokens / max(serve_s, 1e-9), 2),
        "tokens_sha": hashlib.sha256(
            json.dumps(toks).encode()).hexdigest()[:16],
    }))


def run_child(mode, args, aot_dir, cache_dir):
    env = dict(os.environ)
    env.update({"MXTPU_AOT_DIR": aot_dir,
                "JAX_COMPILATION_CACHE_DIR": cache_dir})
    env.pop("MXTPU_WARMUP_MANIFEST", None)  # both modes warm the grid
    if args.platform:
        env["JAX_PLATFORMS"] = args.platform
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--layers", str(args.layers), "--d-model", str(args.d_model),
           "--heads", str(args.heads), "--vocab", str(args.vocab),
           "--block-size", str(args.block_size),
           "--num-blocks", str(args.num_blocks),
           "--max-batch", str(args.max_batch),
           "--max-model-len", str(args.max_model_len),
           "--max-new", str(args.max_new)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                       env=env)
    if r.returncode != 0:
        raise SystemExit(f"{mode} child failed:\n{r.stderr[-2000:]}")
    rec = json.loads([l for l in r.stdout.splitlines()
                      if l.startswith("{")][-1])
    rec["mode"] = mode
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=89)
    p.add_argument("--block-size", type=int, default=4)
    p.add_argument("--num-blocks", type=int, default=64)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-model-len", type=int, default=64)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--json", default=None)
    p.add_argument("--keep-dirs", default=None,
                   help="persist the artifact dirs here (default: tmp)")
    p.add_argument("--backend", "--platform", dest="platform", default=None)
    args = p.parse_args()

    if args.child:
        if args.platform:
            os.environ["JAX_PLATFORMS"] = args.platform
        child(args)
        return

    from tools.bench_io import make_flush

    tmp = args.keep_dirs or tempfile.mkdtemp(prefix="mxtpu_startup_bench_")
    aot_dir = os.path.join(tmp, "aot")
    cache_dir = os.path.join(tmp, "compile_cache")
    os.makedirs(aot_dir, exist_ok=True)
    os.makedirs(cache_dir, exist_ok=True)

    out = {"model": f"gpt{args.layers}x{args.d_model}",
           "max_batch": args.max_batch,
           "max_model_len": args.max_model_len,
           "artifact_dirs": tmp}
    flush = make_flush(args.json, out)
    pts = []
    out["points"] = pts

    cold = run_child("cold", args, aot_dir, cache_dir)
    print(json.dumps(cold))
    pts.append(cold)
    flush(False)
    warm = run_child("warm", args, aot_dir, cache_dir)
    print(json.dumps(warm))
    pts.append(warm)

    out["platform"] = warm["platform"]
    out["device_kind"] = warm["device_kind"]
    out["cold_ready_s"] = cold["ready_s"]
    out["warm_ready_s"] = warm["ready_s"]
    out["warm_over_cold"] = round(warm["ready_s"]
                                  / max(cold["ready_s"], 1e-9), 3)
    out["warm_fresh_traces"] = warm["fresh_traces"]
    out["warm_artifact_loads"] = warm["artifact_loads"]
    out["token_parity"] = cold["tokens_sha"] == warm["tokens_sha"]
    flush(True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
