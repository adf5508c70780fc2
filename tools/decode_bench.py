#!/usr/bin/env python
"""KV-cache decode benchmark: steady-state tokens/sec of the
incremental decoder (models/generate.py).

The inference-side companion to bench.py's training throughput: builds
a checkpoint-shaped random GPT (gpt-small-class by default, plus the
llama-style variant — rope + swiglu + rmsnorm + GQA + tied embeddings)
and measures the compiled KV-cache decode loop at batch 1 and 8.

One ``gpt_generate`` call is one device program (prefill + a
``lax.scan`` over the new tokens) ending in a host fetch, so wall time
includes prefill, dispatch and compile-cache lookup.  The decode rate
is therefore taken from the SLOPE between two trip counts
(``--t1``/``--t2``): tok/s = B * (T2 - T1) / (wall2 - wall1), which
cancels every fixed cost — the same two-trip-count trick
``parallel/collectives._device_loop_s`` uses for in-step loops.

Usage: python tools/decode_bench.py [--json OUT] [--platform cpu]
           [--layers 12 --d-model 768 --heads 12 --vocab 50304 ...]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def make_params(net, B, S, dtype, seed=0):
    """Checkpoint-shaped random params from the symbol's shape
    inference — no executor bind, no training graph."""
    import numpy as np

    arg_shapes, _, _ = net.infer_shape(data=(B, S), softmax_label=(B, S))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 0.02 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale + (
            1.0 if name.endswith("gamma") else 0.0)).astype(dtype)
    return params


def bench_config(mx, np, tag, net, params, B, prompt_len, t1, t2, dtype):
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 64, (B, prompt_len)).astype(np.int32)

    walls = {}
    for T in (t1, t2):
        # warmup compiles (and caches) this T's loop; second call measures
        mx.models.gpt_generate(params, prompt, max_new_tokens=T,
                               symbol=net)
        t0 = time.perf_counter()
        out = mx.models.gpt_generate(params, prompt, max_new_tokens=T,
                                     symbol=net)
        walls[T] = time.perf_counter() - t0
        assert out.shape == (B, prompt_len + T)
    dt = walls[t2] - walls[t1]
    rec = {"config": tag, "batch": B, "prompt_len": prompt_len,
           "t1": t1, "t2": t2, "param_dtype": np.dtype(dtype).name,
           "wall_t1_ms": round(walls[t1] * 1e3, 2),
           "wall_t2_ms": round(walls[t2] * 1e3, 2)}
    if dt > 0:
        rec["decode_tok_per_sec"] = round(B * (t2 - t1) / dt, 1)
        rec["ms_per_token_per_seq"] = round(dt * 1e3 / (t2 - t1), 3)
    else:
        rec["decode_error"] = "non-positive slope (timer noise?)"
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--batches", default="1,8")
    p.add_argument("--t1", type=int, default=32)
    p.add_argument("--t2", type=int, default=160)
    p.add_argument("--dtype", default=None,
                   help="param dtype; default bfloat16 on tpu else float32")
    p.add_argument("--json", default=None)
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import numpy as np

    import mxnet_tpu as mx

    import jax

    on_tpu = jax.default_backend() == "tpu"
    dtype = args.dtype or ("bfloat16" if on_tpu else "float32")
    if dtype == "bfloat16":
        import jax.numpy as jnp

        dtype = jnp.bfloat16
    out = {"platform": jax.default_backend(),
           "device_kind": getattr(jax.devices()[0], "device_kind", ""),
           "layers": args.layers, "d_model": args.d_model,
           "heads": args.heads, "vocab": args.vocab}
    from tools.bench_io import make_flush

    flush = make_flush(args.json, out)
    pts = []
    out["points"] = pts

    S = args.prompt + args.t2
    gpt2 = mx.models.gpt(args.vocab, S, num_layers=args.layers,
                         d_model=args.d_model, num_heads=args.heads)
    kv = max(1, args.heads // 4)
    llama = mx.models.gpt(args.vocab, S, num_layers=args.layers,
                          d_model=args.d_model, num_heads=args.heads,
                          norm="rmsnorm", mlp="swiglu", pos_embed="rope",
                          tie_embeddings=True, kv_heads=kv)
    # params are batch-independent: build each net's set once (the
    # default TPU config is ~124M params — regenerating per batch point
    # would be seconds of redundant host randn per run)
    nets = [("gpt2", gpt2, make_params(gpt2, 1, S, dtype)),
            (f"llama-style/kv{kv}", llama, make_params(llama, 1, S, dtype))]
    for B in (int(x) for x in args.batches.split(",")):
        for tag, net, params in nets:
            rec = bench_config(mx, np, tag, net, params, B,
                               args.prompt, args.t1, args.t2, dtype)
            print(json.dumps(rec))
            pts.append(rec)
            flush(False)
    # stamp completion BEFORE the stdout record: the last line printed
    # is the driver's contract, and a finished run must not say
    # "complete": false there (the artifact write orders the same way)
    flush(True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
