#!/usr/bin/env python3
"""Controls of the diffusion cell's ``correct``: ONE run of the cell as it
is configured (``run.run_cell`` -> ``diffusion_cell.run``), then the same
comparison (``diffusion_cell.check``) of the SAME sampled requests against
a reference that is deliberately NOT the configuration's.  Each must come
out not correct; PERF.md keeps the readings the limits were set between.

    python3 benchmark/diffusion_controls.py --workload <cell> --seed N \\
        --seconds S --variants causal_in_block,shift

The faults enter as data of the comparison: no program of the engine is
recompiled and the timed run is the cell's own.  A regret measures how far
the engine's tokens are from the reference's best, so a reference that
computes another model reads the same distance as an engine that does
would (``reference_diffusion.FAULTS``):

``causal_in_block``  causal inside a block, not bidirectional
``no_qk_norm``       queries and keys without their per-head RMS norms
``shift``            the logits of the position before give a position's
                     token (an autoregressive head)
``left_to_right``    a step unmasks the leftmost masked positions, not the
                     most confident
``stale_kv``         a committed block's K/V are those of its last denoise
                     step, as if no store pass had run
``acc_bf16``         the experts' products summed in bfloat16
``router_bf16``      router logits and probabilities rounded to bfloat16
                     (the nearest precision below the float32 the router
                     states)
"""

import argparse
import json
import sys

import diffusion_cell
import reference_diffusion
import run as run_mod


def run_variants(manifest, workload, config, mix, seed, seconds, variants):
    """The cell's own result, then one verdict per variant on what that
    run sampled."""
    bad = [v for v in variants if v not in reference_diffusion.FAULTS]
    if bad:
        raise SystemExit(f"diffusion_controls: no variant {bad}")
    kept, cells = diffusion_cell.run, []

    def run_and_keep(cell):
        cells.append(cell)
        return kept(cell)

    diffusion_cell.run = run_and_keep
    try:
        res = run_mod.run_cell(manifest, workload, config, mix, seed,
                               seconds, 0)
    finally:
        diffusion_cell.run = kept
    params, seen, probe, span = cells[0]["sampled"]
    verdicts = {v: diffusion_cell.check(params, config, mix["check"], seen,
                                        probe, span, fault=v)
                for v in variants}
    return res, verdicts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variants",
                    default=",".join(reference_diffusion.FAULTS))
    args = ap.parse_args(argv)
    manifest, row, config, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("diffusion_controls.py: no TPU: the limits' readings come "
                 "from the chip")
    res, verdicts = run_variants(manifest, args.workload, config, mix,
                                 args.seed, args.seconds,
                                 args.variants.split(","))
    print(json.dumps({"variant": "sound", "correct": res["correct"],
                      "failed": res["failed"], "metrics": res["metrics"]}),
          flush=True)
    keys = ("max_regret", "mean_regret", "order_regret", "order_regret_max",
            "u_err", "u_err_by_pass", "ffn_err", "kv_err", "schedule_ok",
            "tokens_match")
    for variant, v in verdicts.items():
        print(json.dumps(dict({"variant": variant, "correct": v["ok"]},
                              **{k: v.get(k) for k in keys})), flush=True)


if __name__ == "__main__":
    main()
