#!/usr/bin/env python
"""Pre-bake an AOT artifact directory offline (mxnet_tpu/aot/).

A deploy can pay the trace+compile bill on a build machine instead of
in the serving fleet's critical restart path: point this tool at the
checkpoint and the warmup manifest your production traffic recorded
(``MXTPU_WARMUP_MANIFEST``), ship the resulting ``--aot-dir`` (and the
``JAX_COMPILATION_CACHE_DIR`` the bake ran under) with the release, and
every engine that boots against them loads executables instead of
tracing.

  # bake everything a traffic manifest lists (plus the compile cache)
  JAX_COMPILATION_CACHE_DIR=/release/xla_cache \\
  python tools/aot_warmup.py --aot-dir /release/aot \\
      --checkpoint ckpt/gpt 12 --num-heads 16 \\
      --manifest /var/log/mxtpu_manifest.jsonl

  # no manifest yet: bake the full bucket grid for the config
  python tools/aot_warmup.py --aot-dir /release/aot \\
      --checkpoint ckpt/gpt 12 --num-heads 16

The engine config flags must match production (bucket programs are
fingerprinted by model config + cache geometry + dtype); a mismatch is
harmless — the serving engine skips foreign artifacts and traces fresh
— but the bake is wasted.  ``--synthetic`` swaps the checkpoint for
random weights of a stated shape (CI smoke / artifact-layout tests);
the baked programs are shape-keyed, not weight-keyed, so they are valid
for any checkpoint of that architecture.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--aot-dir", required=True,
                   help="export-store directory to populate")
    p.add_argument("--manifest", default=None,
                   help="warmup manifest JSONL (default: full bucket grid)")
    p.add_argument("--checkpoint", nargs=2, metavar=("PREFIX", "EPOCH"),
                   help="save_checkpoint artifact to serve")
    p.add_argument("--num-heads", type=int, default=None)
    p.add_argument("--window", type=int, default=None)
    p.add_argument("--synthetic", action="store_true",
                   help="random weights instead of a checkpoint")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=89)
    p.add_argument("--block-size", type=int, default=None)
    p.add_argument("--num-blocks", type=int, default=None)
    p.add_argument("--max-batch", type=int, default=None)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--backend", "--platform", dest="platform", default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import numpy as np

    import mxnet_tpu as mx

    import jax

    symbol = None
    num_heads = args.num_heads
    if args.synthetic or not args.checkpoint:
        S = args.max_model_len or 64
        symbol = mx.models.gpt(args.vocab, S, num_layers=args.layers,
                               d_model=args.d_model, num_heads=args.heads)
        arg_shapes, _, _ = symbol.infer_shape(data=(1, S),
                                              softmax_label=(1, S))
        rng = np.random.RandomState(0)
        params = {
            name: (rng.randn(*shp) * (0.02 if name.endswith("weight")
                                      else 0.0)
                   + (1.0 if name.endswith("gamma") else 0.0)
                   ).astype(np.float32)
            for name, shp in zip(symbol.list_arguments(), arg_shapes)
            if name not in ("data", "softmax_label")}
    else:
        prefix, epoch = args.checkpoint[0], int(args.checkpoint[1])
        symbol, arg_params, _ = mx.model.load_checkpoint(prefix, epoch)
        params = {k: v.asnumpy() for k, v in arg_params.items()}

    eng = mx.serve.Engine(
        params, symbol=symbol, num_heads=num_heads, window=args.window,
        block_size=args.block_size, num_blocks=args.num_blocks,
        max_batch=args.max_batch, max_model_len=args.max_model_len,
        aot_dir=args.aot_dir)
    ready = eng.warmup(args.manifest)
    store = mx.aot.ExportStore(args.aot_dir)
    entries = store.entries()
    cache = mx.aot.cache.active()
    print(json.dumps({
        "platform": jax.default_backend(),
        "programs_ready": ready,
        "aot_dir": args.aot_dir,
        "artifacts": len(entries),
        "artifact_bytes": sum(b for _, b in entries),
        "compile_cache": cache.stats() if cache else None,
        "manifest": args.manifest or "full bucket grid",
    }))


if __name__ == "__main__":
    main()
