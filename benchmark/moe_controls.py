#!/usr/bin/env python3
"""Controls of the routed-expert cell's ``correct``: ONE run of the cell
as it is configured (``run.run_cell`` -> ``moe_cell.run``), then the same
comparison (``moe_cell.check``) of the SAME sampled requests against a
reference that is deliberately NOT the configuration's.  Each must come
out not correct; PERF.md keeps the readings the limits were set between.

    python3 benchmark/moe_controls.py --workload <cell> --seed N \\
        --seconds S --variants window_496,held_norm,no_yarn

The faults enter as data of the comparison: no program of the engine is
recompiled and the timed run is the cell's own.  A regret measures how far
the engine's tokens are from the reference's best, so a reference that
computes another model reads the same distance as an engine that does
would (``reference_moe.FAULTS``):

``window_496``   window layers see 496 keys, not 512
``held_norm``    the ten weights normalised over the picks held on this
                 chip only, not over all ten
``no_yarn``      rotary of the full layers at the plain inverse
                 frequencies, without the YaRN blend
``router_bf16``  router logits and probabilities rounded to bfloat16 (the
                 nearest precision below the float32 the layer states)
``acc_bf16``     the experts' products (routed and shared) summed in
                 bfloat16
"""

import argparse
import json
import sys

import moe_cell
import reference_moe
import run as run_mod


def run_variants(manifest, workload, config, mix, seed, seconds, variants):
    """The cell's own result, then one verdict per variant on what that
    run sampled."""
    bad = [v for v in variants if v not in reference_moe.FAULTS]
    if bad:
        raise SystemExit(f"moe_controls: no variant {bad}")
    kept, cells = moe_cell.run, []

    def run_and_keep(cell):
        cells.append(cell)
        return kept(cell)

    moe_cell.run = run_and_keep
    try:
        res = run_mod.run_cell(manifest, workload, config, mix, seed,
                               seconds, 0)
    finally:
        moe_cell.run = kept
    params, finished, live, probe = cells[0]["sampled"]
    verdicts = {v: moe_cell.check(params, config, mix["check"], finished,
                                  live, probe, fault=v) for v in variants}
    return res, verdicts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variants", default=",".join(reference_moe.FAULTS))
    args = ap.parse_args(argv)
    manifest, row, config, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("moe_controls.py: no TPU: the limits' readings come from "
                 "the chip")
    res, verdicts = run_variants(manifest, args.workload, config, mix,
                                 args.seed, args.seconds,
                                 args.variants.split(","))
    print(json.dumps({"variant": "sound", "correct": res["correct"],
                      "failed": res["failed"], "metrics": res["metrics"]}),
          flush=True)
    for variant, v in verdicts.items():
        print(json.dumps({"variant": variant, "correct": v["ok"],
                          "max_regret": v.get("max_regret"),
                          "mean_regret": v.get("mean_regret"),
                          "ffn_err": v.get("ffn_err"),
                          "ffn_err_by_pass": v.get("ffn_err_by_pass"),
                          "u_err": v.get("u_err")}), flush=True)


if __name__ == "__main__":
    main()
