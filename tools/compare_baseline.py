#!/usr/bin/env python
"""Consolidated baseline comparison: read every measurement artifact in
the repo root and print ONE markdown table of metric vs reference
baseline (the judge/README view of ARTIFACTS.md).

Usage: python tools/compare_baseline.py [--repo DIR] [--check [--threshold F]]
Exits 0 with whatever subset of artifacts exists.

``--check`` is the regression gate: for each headline metric, the
CURRENT artifact (BENCH_*_LATEST.json) is compared against the BEST
prior TPU record anywhere in the history (BENCH_r*.json round records,
their embedded best_tpu_record, BENCH_SWEEP.json results); a current
TPU value more than ``--threshold`` (default 5%) below the best prior
exits 1.  Run by tests/test_perf_contract.py, so a committed artifact
that regresses a previous round's measurement fails CI.
"""

import argparse
import glob
import json
import os


def _load(path):
    """Read one artifact: whole-file JSON (possibly indented over many
    lines) or, failing that, the last line of an append-style .jsonl
    log."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        return json.loads(text)
    except ValueError:
        pass
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except ValueError:
        return None


def rows_from(repo):
    rows = []

    def bench_row(fname, label):
        rec = _load(os.path.join(repo, fname))
        if rec and rec.get("platform") == "tpu":
            extra = ""
            if rec.get("mfu"):
                extra = f"{rec['mfu'] * 100:.1f}% MFU"
            if rec.get("vs_baseline_per_peak_tflop"):
                extra += (f"; {rec['vs_baseline_per_peak_tflop']:.2f}x "
                          "per peak TFLOP")
            rows.append((label, f"{rec['value']:.0f} {rec['unit']}",
                         f"{rec['vs_baseline']:.3f}x", extra))

    bench_row("BENCH_TPU_LATEST.json", "ResNet-50 train (vs A100 2500 img/s)")
    bench_row("BENCH_GPT_LATEST.json", "GPT train (vs A100 400k tok/s)")
    bench_row("BENCH_CIFAR_LATEST.json",
              "CIFAR inception-bn (vs ref 4-GPU box 2943 img/s)")

    quant = _load(os.path.join(repo, "QUANT_BENCH.json"))
    if quant and quant.get("platform") == "tpu":
        rows.append(("int8 inference speedup (vs own float)",
                     f"{quant['int8_img_per_sec']:.0f} img/s",
                     f"{quant['int8_speedup']:.2f}x", "full int8"))

    flash = _load(os.path.join(repo, "FLASH_BENCH.json"))
    if flash and flash.get("platform") == "tpu":
        sp = [p.get("speedup") for p in flash.get("points", [])
              if p.get("speedup")]
        if sp:
            rows.append(("flash attention (vs dense XLA)", "—",
                         f"up to {max(sp):.2f}x",
                         f"{len(sp)} shapes"))

    rnn = _load(os.path.join(repo, "RNN_BENCH.json"))
    if rnn and rnn.get("platform") == "tpu":
        sp = [p.get("speedup") for p in rnn.get("points", [])
              if p.get("speedup") and p.get("eligible")]
        if sp:
            rows.append(("fused RNN (vs lax.scan cell)", "—",
                         f"up to {max(sp):.2f}x",
                         f"{len(sp)} shapes"))

    io_rec = _load(os.path.join(repo, "IO_BENCH.json"))
    if io_rec:
        rows.append(("image pipeline (vs ref 250 img/s/core)",
                     f"{io_rec['value']:.0f} img/s",
                     f"{io_rec.get('vs_baseline_per_core', 0):.2f}x/core",
                     f"{io_rec.get('host_cores')} host core(s)"))

    bw = _load(os.path.join(repo, "BANDWIDTH.json"))
    if bw and bw.get("platform") == "tpu":
        rows.append(("collective/memory bandwidth", "see BANDWIDTH.json",
                     "—", bw.get("device_kind", "")))
    return rows


# metric -> the artifact holding its CURRENT measurement
LATEST_ARTIFACTS = {
    "resnet50_train_throughput": "BENCH_TPU_LATEST.json",
    "gpt_train_throughput": "BENCH_GPT_LATEST.json",
    "cifar_inception_bn_small_train_throughput": "BENCH_CIFAR_LATEST.json",
}


def _tpu_records(rec, metric):
    """Every TPU measurement of ``metric`` reachable from one artifact
    payload: the record itself, an embedded best_tpu_record, and sweep
    result lists."""
    if not isinstance(rec, dict):
        return
    if (rec.get("metric") == metric and rec.get("platform") == "tpu"
            and "error" not in rec and rec.get("value")):
        yield float(rec["value"])
    embedded = rec.get("best_tpu_record")
    if isinstance(embedded, dict) and embedded.get("value") and (
            rec.get("metric") == metric):
        yield float(embedded["value"])
    for child in rec.get("results", []):
        yield from _tpu_records(child, metric)
    for child in rec.values():
        # sweep best_* entries (explicit metric match only)
        if isinstance(child, dict) and "config" in child and \
                child.get("metric") == metric and \
                child.get("platform") == "tpu" and child.get("value"):
            yield float(child["value"])


def check(repo, threshold):
    """Regression gate; returns a list of failure strings."""
    failures = []
    history = sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))) + [
        os.path.join(repo, "BENCH_SWEEP.json")]
    for metric, latest_name in LATEST_ARTIFACTS.items():
        cur_rec = _load(os.path.join(repo, latest_name))
        if not cur_rec or cur_rec.get("platform") != "tpu":
            continue                    # nothing current to gate
        cur = float(cur_rec.get("value", 0))
        prior = [v for path in history
                 for v in _tpu_records(_load(path), metric)]
        if not prior:
            continue
        best = max(prior)
        if cur < best * (1.0 - threshold):
            failures.append(
                f"{metric}: current {cur:.1f} ({latest_name}) is "
                f"{(1 - cur / best) * 100:.1f}% below best prior {best:.1f} "
                f"(threshold {threshold * 100:.0f}%)")
    return failures


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--repo",
                   default=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    p.add_argument("--check", action="store_true",
                   help="regression gate: exit 1 if a current artifact "
                        "regresses the best prior TPU record")
    p.add_argument("--threshold", type=float, default=0.05)
    args = p.parse_args()
    if args.check:
        failures = check(args.repo, args.threshold)
        for f in failures:
            print(f"REGRESSION: {f}")
        if failures:
            raise SystemExit(1)
        print("regression gate: OK")
        return
    rows = rows_from(args.repo)
    print("| Metric | Measured | vs baseline | Notes |")
    print("|---|---|---|---|")
    for label, value, ratio, notes in rows:
        print(f"| {label} | {value} | {ratio} | {notes} |")
    if not rows:
        print("| (no TPU artifacts captured yet) | — | — | see "
              "ARTIFACTS.md for producers |")


if __name__ == "__main__":
    main()
