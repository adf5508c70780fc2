#!/usr/bin/env python3
"""Controls of the one-branch cell's ``correct``: ONE run of the cell as it
is configured (``run.run_cell`` -> ``branch_cell.run``), then the same
comparison (``branch_cell.check``) of the SAME sampled requests, states and
probe rows against a reference that is deliberately NOT the
configuration's.  Each must come out not correct, or is reported as not
telling; PERF.md keeps the readings the limits were set between.

    python3 benchmark/branch_controls.py --workload <cell> --seed N \\
        --seconds S --variants one_group,norm_whole,relu_plain

The faults enter as data of the comparison: no program of the engine is
recompiled and the timed run is the cell's own.  A distance is symmetric,
so a reference that computes another model reads what an engine that does
would (``reference_branch.FAULTS``):

``one_group``    every state-space head reads the FIRST group's B and C
                 rows: one state group instead of eight
``norm_whole``   the gated norm's RMS over all of d_inner, not over each
                 group's channels
``kv_swapped``   the groups of query heads read the key-value heads in
                 reverse order (2 kv heads under 32 query heads: each
                 group of 16 reads the other's): a wrong head mapping in
                 the paged or the span kernel
``relu_plain``   the experts' (and the shared expert's) ReLU not squared
``scale_1``      the routed weights without the scaling factor
``no_bias``      the 22 picks by the sigmoids alone, without the selection
                 bias
``acc_bf16``     the experts' products (routed and shared) summed in
                 bfloat16 (the nearest precision below the float32
                 accumulation the layer states)
``state_bf16``   the recurrent state rounded to bfloat16 after every
                 token: what a pool kept in bfloat16 holds

``--engine-variants state_bf16`` makes one MORE run of the cell on an
engine whose state pool is rounded to bfloat16 after every step
(``hybrid_controls.round_state_after_every_step``: the fault on the
engine's side, where ``state_f32_share`` can see it).
"""

import argparse
import gc
import json
import sys

import branch_cell
import reference_branch
import run as run_mod

KEYS = ("max_regret", "mean_regret", "state_err", "state_err_first",
        "state_err_by_layer", "state_f32_share", "u_err_by_layer",
        "ffn_err", "ffn_err_by_pass", "u_err", "pick_flips", "why")


def run_variants(manifest, workload, config, mix, seed, seconds, variants):
    """The cell's own result, then one verdict per variant on what that
    run sampled."""
    bad = [v for v in variants if v not in reference_branch.FAULTS]
    if bad:
        raise SystemExit(f"branch_controls: no variant {bad}")
    kept, cells = branch_cell.run, []

    def run_and_keep(cell):
        cells.append(cell)
        return kept(cell)

    branch_cell.run = run_and_keep
    try:
        res = run_mod.run_cell(manifest, workload, config, mix, seed,
                               seconds, 0)
    finally:
        branch_cell.run = kept
    params, finished, live, live_states, probe = cells[0]["sampled"]
    verdicts = {}
    for v in variants:
        verdicts[v] = branch_cell.check(params, config, mix["check"],
                                        finished, live, live_states, probe,
                                        fault=v)
        gc.collect()
    return res, verdicts


def run_rounded_state(manifest, workload, config, mix, seed, seconds):
    """One run of the cell on an engine whose recurrent states are rounded
    to bfloat16 after every step."""
    import hybrid_controls

    kept = branch_cell.build

    def build(cfg, seed):
        dec, params, eng = kept(cfg, seed)
        hybrid_controls.round_state_after_every_step(eng)
        return dec, params, eng

    branch_cell.build = build
    try:
        return run_mod.run_cell(manifest, workload, config, mix, seed,
                                seconds, 0)
    finally:
        branch_cell.build = kept
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variants", default=",".join(reference_branch.FAULTS))
    ap.add_argument("--engine-variants", default="")
    args = ap.parse_args(argv)
    manifest, row, config, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("branch_controls.py: no TPU: the limits' readings come "
                 "from the chip")
    variants = [v for v in args.variants.split(",") if v]
    res, verdicts = run_variants(manifest, args.workload, config, mix,
                                 args.seed, args.seconds, variants)
    print(json.dumps({"variant": "sound", "correct": res["correct"],
                      "failed": res["failed"], "metrics": res["metrics"]}),
          flush=True)
    for variant, v in verdicts.items():
        print(json.dumps(dict({"variant": variant, "correct": v["ok"]},
                              **{k: v.get(k) for k in KEYS})), flush=True)
    if "state_bf16" in args.engine_variants.split(","):
        res = run_rounded_state(manifest, args.workload, config, mix,
                                args.seed, args.seconds)
        print(json.dumps({"variant": "engine:state_bf16",
                          "correct": res["correct"],
                          "failed": res["failed"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
