#!/usr/bin/env python
"""Terminal "where did the time go" breakdown over the serve engine's
performance-attribution plane (``telemetry/perf_attrib.py``).

Reads a live ``/statusz.json`` endpoint or a saved snapshot (an engine
``statusz()`` dict, a full statusz page, a replica scrape, or a
serve_bench record that embedded one — any JSON containing a ``perf``
section) and renders, per engine: how many steps were timed, the
overall goodput line (timed device seconds, MFU, achieved TFLOP/s,
device cost per 1k tokens), and the per-program table sorted by share
of the exactly timed device time — the enumerable answer to "which
program family do I optimize next".  The step record times every pass
while telemetry is on (``telemetry/profiling.py``, "The pass record").

With telemetry off the cost table still prints: flops and bytes per
(kind, bucket) from ``cost_analysis()``, dispatch counts, but no
device-time columns.  Pure stdlib.

Usage:
  python tools/perf_report.py --url http://host:port
  python tools/perf_report.py --file statusz.json [--json OUT]
"""

import argparse
import json
import sys
import urllib.request


def fetch(url, timeout=5.0):
    with urllib.request.urlopen(f"{url.rstrip('/')}/statusz.json",
                                timeout=timeout) as resp:
        return json.loads(resp.read())


def find_perf_sections(obj, path="$"):
    """Every perf-attribution section in a JSON tree, as
    ``[(path, section)]`` — a section is a dict carrying both
    ``programs`` and ``timed_steps`` (the PerfAttrib.statusz shape)."""
    out = []
    if isinstance(obj, dict):
        if "programs" in obj and "timed_steps" in obj:
            out.append((path, obj))
        else:
            for k, v in obj.items():
                out.extend(find_perf_sections(v, f"{path}.{k}"))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.extend(find_perf_sections(v, f"{path}[{i}]"))
    return out


def _fmt(v, nd=2):
    if v is None:
        return "-"
    return f"{v:.{nd}f}"


def _fmt_us(seconds):
    if seconds is None:
        return "-"
    return f"{seconds * 1e6:.0f}"


def _fmt_count(v, unit=1e9, nd=2):
    if v is None:
        return "-"
    return f"{v / unit:.{nd}f}"


def render(path, perf):
    lines = [f"perf section at {path}:"]
    lines.append(
        f"  timed_steps={perf.get('timed_steps')}"
        f" tokens={perf.get('tokens')}"
        f" timed_tokens={perf.get('timed_tokens')}"
        f" cost_errors={perf.get('cost_errors')}")
    mfu = perf.get("mfu")
    lines.append(
        f"  goodput: device_s={_fmt(perf.get('device_seconds'), 4)}"
        f" achieved_tflops={_fmt(perf.get('achieved_tflops'), 4)}"
        f" mfu={_fmt(100 * mfu if mfu is not None else None, 2)}%"
        f" tok_flops={_fmt_count(perf.get('tok_flops'), 1e6)}M"
        f" cost/1k_tok={_fmt(perf.get('cost_per_1k_tokens_s'), 4)}s")
    peak = perf.get("peak_flops_per_chip")
    lines.append(
        f"  peaks: flops/chip="
        f"{_fmt_count(peak, 1e12) if peak else '-'}T"
        f" hbm={_fmt_count(perf.get('peak_hbm_bytes_per_chip'), 1e9)}GB/s")
    lines.append("")
    lines.append(
        f"  {'KIND':<12} {'BUCKET':>6} {'DISP':>7} {'TIMED':>7} "
        f"{'MEAN_US':>8} {'P99_US':>8} {'SHARE%':>6} {'GFLOP':>8} "
        f"{'GB':>7} {'TFLOP/S':>8} {'MFU%':>6} {'SRC':<13}")
    rows = sorted(perf.get("programs") or [],
                  key=lambda r: -(r.get("share") or 0.0))
    for r in rows:
        share = r.get("share")
        rmfu = r.get("mfu")
        lines.append(
            f"  {str(r.get('kind')):<12} {r.get('bucket'):>6} "
            f"{r.get('dispatches', 0):>7} {r.get('timed', 0):>7} "
            f"{_fmt_us(r.get('mean_s')):>8} "
            f"{_fmt_us(r.get('p99_s')):>8} "
            f"{_fmt(100 * share if share is not None else None, 1):>6} "
            f"{_fmt_count(r.get('flops')):>8} "
            f"{_fmt_count(r.get('bytes_accessed')):>7} "
            f"{_fmt(r.get('achieved_tflops'), 3):>8} "
            f"{_fmt(100 * rmfu if rmfu is not None else None, 2):>6} "
            f"{str(r.get('source') or '-'):<13}")
    if not rows:
        lines.append("  (cost table empty — engine has resolved no "
                     "programs yet, or MXTPU_PERF_ATTRIB=0)")
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="per-program serve-engine time/FLOP attribution")
    p.add_argument("--url", default=None,
                   help="statusz base URL (http://host:port)")
    p.add_argument("--file", default=None,
                   help="render a saved statusz/perf JSON instead")
    p.add_argument("--json", default=None,
                   help="also write the extracted perf sections as JSON")
    args = p.parse_args(argv)
    if bool(args.url) == bool(args.file):
        p.error("pass exactly one of --url / --file")
    if args.file:
        with open(args.file) as f:
            doc = json.load(f)
    else:
        try:
            doc = fetch(args.url)
        except (OSError, ValueError) as e:
            print(f"statusz unreachable: {e}", file=sys.stderr)
            return 1
    sections = find_perf_sections(doc)
    if not sections:
        print("no perf sections found (MXTPU_PERF_ATTRIB=0, or not an "
              "engine statusz document)", file=sys.stderr)
        return 1
    print("\n\n".join(render(path, perf) for path, perf in sections))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(sections), f, indent=2, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
