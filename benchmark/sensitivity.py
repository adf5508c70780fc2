#!/usr/bin/env python3
"""How far an open-loop cell's tails move when the fixed pattern of
arrivals is nudged, once, on the chip.

    python3 benchmark/sensitivity.py --workload mistral7b-l16.chat-steady \\
        --seconds 50 --seed 1 \\
        --conditions base,base,scale=1.01,scale=0.98,delay=0.05,delay=0.1,stall=3

The order of shapes and gaps is frozen in the traffic file, so repeated
runs of a cell say how well ONE pattern repeats, not how much of a tail
is the luck of which prefill lands in front of whom.  A change to the
program shifts every step boundary and re-rolls those collisions.  This
tool runs the cell's window several times in one process (one set-up; the
engine drained and the token ids new between windows) under the nudges
such a change causes:

  base        the cell as it is
  scale=x     every due instant multiplied by x (the same requests, the
              schedule stretched by a percent or two)
  delay=f     every engine step followed by a host sleep of f x its own
              duration (a uniformly slower program)
  stall=s     one host stall of s seconds at 85 % of the window (what a
              late backlog does to the tails)

and prints one ``{"window": ...}`` line each with the tails as the cell
defines them and, beside them, as they read over the requests that
FINISHED in the window only (the rule this benchmark first had, which a
late stall improves).  ``--check-seeds n`` first serves the mix's check
sequences under n seeds of token ids and prints the worst and the mean
regret of each, from which ``serve_cell``'s limits were set.
"""

import argparse
import json
import sys
import time

import arith
import run as run_mod
import serve_cell
import traffic as traffic_mod


class Nudged:
    """The engine with a sleep after every step and, once, a stall."""

    def __init__(self, eng, delay, stall_s):
        self._eng, self._delay, self._stall_s = eng, delay, stall_s
        self.stall_at = None

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def step(self):
        tic = time.perf_counter()
        out = self._eng.step()
        now = time.perf_counter()
        if self._delay:
            time.sleep((now - tic) * self._delay)
        if self._stall_s and self.stall_at and now >= self.stall_at:
            time.sleep(self._stall_s)
            self._stall_s = 0.0
        return out


def finished_only(out):
    """(TTFTs, TPOTs) in ms over the requests that finished inside the
    window, whenever they were due."""
    fin = [r for r in out["all"] if r.finish_t is not None and not r.failed
           and out["start"] <= r.finish_t <= out["end"]]
    return ([(r.first_t - r.due) * 1e3 for r in fin],
            [(r.last_t - r.first_t) / (r.seen - 1) * 1e3 for r in fin
             if r.seen > 1])


def windows(eng, cfg, mix, conditions, seconds, seed):
    """Run one window per condition on a warmed engine; yields one dict
    each."""
    for i, cond in enumerate(conditions):
        kind, _, value = cond.partition("=")
        value = float(value) if value else 0.0
        loop = traffic_mod.loop(mix, seed + i, seconds, cfg["vocab_size"])
        if kind == "scale":
            loop.due = loop.due * value
        nudged = Nudged(eng, value if kind == "delay" else 0.0,
                        value if kind == "stall" else 0.0)

        def on_window():
            nudged.stall_at = time.perf_counter() + 0.85 * seconds

        out = serve_cell.drive(nudged, loop, seconds, on_window=on_window)
        ttft, tpot = serve_cell.latencies_ms(out)
        ttft_f, tpot_f = finished_only(out)
        recs = out["records"]
        sizes = [s[5] for s in out["steps"] if s[5]]
        yield {
            "condition": cond, "due_in_window": len(recs),
            "finished_in_window": out["finished_in_window"],
            "unfinished": sum(r.finish_t is None for r in recs),
            "no_token_yet": sum(r.first_t is None for r in recs),
            "failed": sum(r.failed for r in recs),
            "ttft_ms_p90": arith.percentile(ttft, 90),
            "tpot_ms_p90": arith.percentile(tpot, 90),
            "ttft_ms_p50": arith.percentile(ttft, 50),
            "tpot_ms_p50": arith.percentile(tpot, 50),
            "finished_only": {"n": len(ttft_f),
                              "ttft_ms_p90": arith.percentile(ttft_f, 90),
                              "tpot_ms_p90": arith.percentile(tpot_f, 90)},
            "out_tok_s": out["tokens"] / out["window_s"],
            "decode_batch_mean": sum(sizes) / max(1, len(sizes)),
            "queue_max": max([s[6] for s in out["steps"]] or [0])}
        eng.run()                      # drain before the next window


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--conditions", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--check-seeds", type=int, default=0)
    args = ap.parse_args()

    _, row, cfg, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) != row["chips"]:
        sys.exit("sensitivity.py: needs the cell's TPU chips")
    net, params, eng = serve_cell.build(cfg, args.seed)
    first = traffic_mod.loop(mix, args.seed, args.seconds, cfg["vocab_size"])
    lens = list(first.prompt_len) + [p for p, _ in mix["check"]["sequences"]]
    eng.warmup([{"kind": k, "bucket": b}
                for k, b in serve_cell.programs_for(lens, cfg["engine"])])
    for i in range(args.check_seeds):
        print(json.dumps({"check": serve_cell.check(
            eng, params, cfg, mix["check"], args.seed + 1000 * i)}),
            flush=True)
    for res in windows(eng, cfg, mix, args.conditions.split(","),
                       args.seconds, args.seed):
        print(json.dumps({"window": res}), flush=True)
    eng.shutdown()


if __name__ == "__main__":
    main()
