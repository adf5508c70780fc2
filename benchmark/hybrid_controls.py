#!/usr/bin/env python3
"""Controls of the hybrid cell's ``correct``: the same cell, the same
window, the same comparison (``run.run_cell`` -> ``hybrid_cell.run`` ->
``hybrid_cell.check``), on an engine that is deliberately NOT the
configuration's.  Each must come out not correct; PERF.md keeps the
readings the limits were set between.

    python3 benchmark/hybrid_controls.py --workload <cell> --seed N \\
        --seconds S --variants state_bf16,scale_8x

The reference is always fed the configuration's own weights.  No variant
compiles a program the cell does not (the faults enter as data):

``sound``        the configuration as it is (the reading to set beside)
``state_bf16``   the recurrent-state pool rounded to bfloat16 after every
                 step: what a pool kept in bfloat16 holds, bit for bit (the
                 configuration states float32; this is the nearest
                 precision below).  By ``lax.reduce_precision``: ``astype``
                 twice rounded nothing on the chip (my chip run, PR 29: the
                 pool came back with 99.97 % of its low mantissa bits in
                 use; a float32 -> bfloat16 -> float32 round trip inside
                 one fusion may be kept in float32 there)
``scale_8x``     attention scores 8 times too large: the engine's query
                 rows are multiplied by 8, which is ``1/sqrt(head)`` = 1/8
                 for the published ``attention_multiplier`` 1/64
``no_residual``  the residual multiplier left out: the engine's mixer and
                 MLP output rows are divided by it
"""

import argparse
import gc
import json
import sys

import hybrid_cell
import run as run_mod

VARIANTS = ("sound", "state_bf16", "scale_8x", "no_residual")


def faulty_params(dec, params, variant):
    """The weights the ENGINE is built on."""
    import jax.numpy as jnp

    out = dict(params)
    for i, kind in enumerate(dec.layer_types):
        p = f"{dec.name}_l{i}"
        if variant == "scale_8x" and kind == "attention":
            w, q = out[f"{p}_qkv_weight"], dec.num_heads * dec.head_dim
            out[f"{p}_qkv_weight"] = jnp.concatenate([w[:q] * 8, w[q:]], 0)
        if variant == "no_residual":
            stems = ("proj" if kind == "attention" else "out_proj", "ff_out")
            for stem in stems:
                w = out[f"{p}_{stem}_weight"]
                out[f"{p}_{stem}_weight"] = (
                    w.astype(jnp.float32) / dec.residual_multiplier
                ).astype(w.dtype)
    return out


def round_state_after_every_step(eng):
    import jax

    rounded = jax.jit(
        lambda s: jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7),
        donate_argnums=0 if eng._donate else ())
    step = eng.step

    def step_then_round():
        out = step()
        eng._state_ssm = rounded(eng._state_ssm)
        return out

    eng.step = step_then_round


def build_of(variant):
    def build(cfg, seed):
        dec = hybrid_cell.describe(cfg)
        params = hybrid_cell.make_params(dec, cfg["dtype"], seed)
        eng = hybrid_cell.engine(cfg, dec,
                                 faulty_params(dec, params, variant))
        if variant == "state_bf16":
            round_state_after_every_step(eng)
        return dec, params, eng
    return build


def run_variant(manifest, workload, config, mix, seed, seconds, variant):
    """One run of the cell with the variant's engine: the result object."""
    if variant not in VARIANTS:
        raise SystemExit(f"hybrid_controls: no variant {variant!r}")
    kept = hybrid_cell.build
    hybrid_cell.build = build_of(variant)
    try:
        return run_mod.run_cell(manifest, workload, config, mix, seed,
                                seconds, 0)
    finally:
        hybrid_cell.build = kept
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variants", default="state_bf16,scale_8x")
    args = ap.parse_args(argv)
    manifest, row, config, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("hybrid_controls.py: no TPU: the limits' readings come "
                 "from the chip")
    for variant in args.variants.split(","):
        run_mod.info(variant=variant)
        res = run_variant(manifest, args.workload, config, mix, args.seed,
                          args.seconds, variant)
        print(json.dumps({"variant": variant, "correct": res["correct"],
                          "failed": res["failed"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
