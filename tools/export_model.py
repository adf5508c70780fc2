#!/usr/bin/env python
"""One-command deploy export: checkpoint -> single .mxa artifact.

The artifact (a STORED zip) carries symbol.json + params.npz +
serialized StableHLO + manifest and serves BOTH deploy consumers:

- ``mxnet_tpu.predict.load_exported`` (jax + numpy only), and
- the amalgamation C runtime (``amalgamation/mxtpu_predict.c``) — one
  C file + this artifact, no Python tree, the reference amalgamation/
  story (predict-only single-file build, c_predict_api.cc:1-305).

Usage:
  python tools/export_model.py --prefix model --epoch 3 \
      --data-shape 1,1,28,28 --out model.mxa [--dtype float32]
"""

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--prefix", required=True,
                   help="checkpoint prefix (model.save_checkpoint)")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--data-shape", required=True,
                   help="comma-separated, e.g. 1,1,28,28")
    p.add_argument("--data-name", default="data")
    p.add_argument("--out", default=None, help="default: <prefix>.mxa")
    p.add_argument("--dtype", default=None,
                   help="cast params (e.g. bfloat16); default keep")
    p.add_argument("--platforms", default=None,
                   help="comma list for the StableHLO leg (e.g. cpu,tpu)")
    args = p.parse_args()

    import mxnet_tpu as mx

    sym, arg_params, aux_params = mx.model.load_checkpoint(
        args.prefix, args.epoch)
    shape = tuple(int(x) for x in args.data_shape.split(","))
    out = args.out or (args.prefix + ".mxa")
    mx.predict.export_model(
        out, sym, arg_params, aux_params,
        {args.data_name: shape}, dtype=args.dtype,
        platforms=args.platforms.split(",") if args.platforms else None)
    print(f"exported {out} ({os.path.getsize(out)} bytes): "
          f"symbol.json + params.npz + StableHLO; consumable by "
          f"mx.predict.load_exported OR amalgamation/mxtpu_predict.c")


if __name__ == "__main__":
    main()
