#!/usr/bin/env python
"""Throughput sweep over bench.py configurations.

Runs the ResNet-50 benchmark across layout/stem/batch/dtype combos (and
the GPT mode) as child processes, ONE AT A TIME (each child holds the
chip while it runs; this parent never imports jax, so it never does),
collects each one-line JSON result, and writes ``BENCH_SWEEP.json``
with every point plus the best config.

Usage:  python tools/bench_sweep.py [--out BENCH_SWEEP.json] [--quick]
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_rev():
    try:
        r = subprocess.run(["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, timeout=10)
        return r.stdout.strip() or None
    except Exception:
        return None


def run_point(env_overrides, timeout=2400):
    env = dict(os.environ)
    env.update(env_overrides)
    try:
        r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                           capture_output=True, text=True, timeout=timeout,
                           env=env)
    except subprocess.TimeoutExpired:
        return {"config": env_overrides, "error": "timeout"}
    for line in r.stdout.splitlines():
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # log noise that happens to start with a brace
        rec["config"] = env_overrides
        return rec
    return {"config": env_overrides,
            "error": (r.stderr or "no output")[-500:]}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default=os.path.join(REPO, "BENCH_SWEEP.json"))
    p.add_argument("--quick", action="store_true",
                   help="one batch size per config")
    p.add_argument("--fresh", action="store_true",
                   help="ignore an existing --out file and re-measure every "
                        "point (default: keep its good results and only run "
                        "missing/failed points)")
    args = p.parse_args()

    # ONE grid definition; --quick runs the subset marked quick=True.
    # BENCH_FUSED_QKV is explicit in gpt configs so a compute-path change
    # there reads as a NEW config (merge mode won't keep stale records).
    def grid_points():
        for layout, stem in (("NHWC", "s2d"), ("NHWC", "conv7"),
                             ("NCHW", "conv7")):
            for bs in ("64", "128", "256", "512"):
                yield ({"BENCH_LAYOUT": layout, "BENCH_STEM": stem,
                        "BENCH_BATCH": bs}, bs == "128")
        for bs in ("8", "16", "32"):
            yield ({"BENCH_MODEL": "gpt", "BENCH_BATCH": bs,
                    "BENCH_FUSED_QKV": "1"}, bs == "16")
        # sequence-major attention: kernel indexes the head dim, so the
        # per-layer BSHD<->BHSD activation transposes (the only
        # activation transposes in the step HLO) disappear
        yield ({"BENCH_MODEL": "gpt", "BENCH_BATCH": "16",
                "BENCH_FUSED_QKV": "1",
                "BENCH_ATTN_LAYOUT": "bshd"}, False)
        # grouped-query attention: kv_heads=2 of 8 — smaller K/V
        # projections + (bshd) kernel K/V streams
        yield ({"BENCH_MODEL": "gpt", "BENCH_BATCH": "16",
                "BENCH_FUSED_QKV": "1", "BENCH_ATTN_LAYOUT": "bshd",
                "BENCH_KV_HEADS": "2"}, False)
        # fused CE head: no (B*S, 32768) probability tensor in HBM
        yield ({"BENCH_MODEL": "gpt", "BENCH_BATCH": "16",
                "BENCH_FUSED_QKV": "1", "BENCH_GPT_LOSS": "ce"}, False)
        # the full modern recipe: llama style + GQA + CE + bshd
        yield ({"BENCH_MODEL": "gpt", "BENCH_BATCH": "16",
                "BENCH_FUSED_QKV": "1", "BENCH_ATTN_LAYOUT": "bshd",
                "BENCH_KV_HEADS": "2", "BENCH_GPT_LOSS": "ce",
                "BENCH_GPT_STYLE": "llama"}, False)
        for bs in ("256", "512", "1024"):
            yield ({"BENCH_MODEL": "cifar", "BENCH_BATCH": bs},
                   bs == "512")
        # XLA flag experiments on the best-known config: scoped-VMEM
        # headroom lets the fusion cost model build larger fusions
        # (public TPU perf knob); ineffective flags reproduce the base
        for kib in ("32768", "65536"):
            yield ({"BENCH_LAYOUT": "NHWC", "BENCH_STEM": "s2d",
                    "BENCH_BATCH": "128",
                    "LIBTPU_INIT_ARGS":
                        f"--xla_tpu_scoped_vmem_limit_kib={kib}"}, False)
        # optimizer-state dtype: f32 momentum doubles optimizer HBM
        # traffic vs the bf16 default — measures how update-phase-bound
        # the step is
        yield ({"BENCH_LAYOUT": "NHWC", "BENCH_STEM": "s2d",
                "BENCH_BATCH": "128",
                "BENCH_OPT_STATE_DTYPE": "float32"}, False)
        # latency-hiding scheduler: overlaps collective/copy latency
        # with compute inside the step program (public TPU perf knob)
        yield ({"BENCH_LAYOUT": "NHWC", "BENCH_STEM": "s2d",
                "BENCH_BATCH": "128",
                "LIBTPU_INIT_ARGS":
                    "--xla_tpu_enable_latency_hiding_scheduler=true"},
               False)
        # whole timed loop on device (fori_loop over the train step):
        # removes any per-dispatch queue gap — if this beats the
        # default mode, the gap was dispatch, not compute
        yield ({"BENCH_LAYOUT": "NHWC", "BENCH_STEM": "s2d",
                "BENCH_BATCH": "128", "BENCH_DEVICE_LOOP": "1"}, False)

    full_grid = [pt for pt, _ in grid_points()]
    todo = [pt for pt, quick in grid_points() if quick or not args.quick]
    results = []
    rev = _git_rev()
    if not args.fresh and os.path.exists(args.out):
        prior = json.load(open(args.out)).get("results", [])
        # only real-hardware measurements count as done: a platform:cpu
        # record must not mask the point on the next chip run.
        # Records whose config left the grid are dropped so a removed
        # configuration can never win "best".
        good = [r for r in prior
                if "error" not in r and r.get("platform") == "tpu"
                and r.get("config") in full_grid]
        done = [r.get("config") for r in good]
        results = list(good)
        todo = [pt for pt in todo if pt not in done]
        print(f"merge mode: {len(good)} good points kept, "
              f"{len(todo)} to (re)run (--fresh to re-measure all)")
        stale = sorted({r.get("git_rev") for r in good
                        if r.get("git_rev") not in (None, rev)})
        if stale:
            n_stale = sum(1 for r in good
                          if r.get("git_rev") not in (None, rev))
            print(f"WARNING: {n_stale} kept points were measured at other "
                  f"revision(s) {stale} (current {rev}); pass --fresh if "
                  "the compute path changed", file=sys.stderr)
        if not todo:
            print("WARNING: nothing to measure — every grid point is "
                  "already recorded; pass --fresh to re-measure",
                  file=sys.stderr)

    for pt in todo:
        rec = run_point(pt)
        rec["git_rev"] = rev
        results.append(rec)
        print(json.dumps(rec))
        # incremental write: a crash mid-sweep keeps completed points
        with open(args.out, "w") as f:
            json.dump({"results": results, "partial": True}, f, indent=1)

    def best_of(metric):
        cands = [r for r in results if r.get("metric") == metric]
        return max(cands, key=lambda r: r.get("value", 0), default=None)

    out = {"results": results,
           "best_resnet50": best_of("resnet50_train_throughput"),
           "best_gpt": best_of("gpt_train_throughput"),
           "best_cifar": best_of(
               "cifar_inception_bn_small_train_throughput")}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    for key in ("best_resnet50", "best_gpt", "best_cifar"):
        if out[key]:
            print(f"{key}:", json.dumps(out[key]))


if __name__ == "__main__":
    main()
