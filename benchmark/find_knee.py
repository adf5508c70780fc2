#!/usr/bin/env python3
"""Find the knee of an open-loop mix once, on the chip: the highest rate
at which nothing is rejected and the waiting queue does not grow over the
second half of the step.

    python3 benchmark/find_knee.py --workload mistral7b-l16.chat-steady \\
        --rates 1.0,1.5,2.0,2.5,3.0,3.5 --seconds 25 --seed 1

One process and one set-up; the engine is drained between rates.  Prints
one ``{"sweep": ...}`` line per rate and a last ``{"knee": ...}`` line.
The cell's ``rate`` is then 0.8 x the knee, written into the traffic file
by hand (the benchmark offers a fixed rate and never searches for one).
"""

import argparse
import json
import sys

import run as run_mod
import arith
import serve_cell
import traffic as traffic_mod


def sustained(row):
    return row["failed"] == 0 and row["queue_q4"] <= row["queue_q2"] + 2.0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]

    _, row, cfg, mix = run_mod.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) != row["chips"]:
        sys.exit("find_knee.py: needs the cell's TPU chips")
    net, params, eng = serve_cell.build(cfg, args.seed)
    loops = [traffic_mod.loop(dict(mix, rate=r), args.seed + i, args.seconds,
                              cfg["vocab_size"]) for i, r in enumerate(rates)]
    lens = [p for lp in loops for p in lp.prompt_len]
    eng.warmup([{"kind": k, "bucket": b}
                for k, b in serve_cell.programs_for(lens, cfg["engine"])])
    best = None
    for rate, loop in zip(rates, loops):
        out = serve_cell.drive(eng, loop, args.seconds)
        steps = out["steps"]
        fin = out["finished_in_window"]
        bad = sum(r.failed for r in out["records"])
        q = [s[6] for s in steps]
        quarter = max(1, len(q) // 4)
        mean = lambda xs: sum(xs) / max(1, len(xs))
        ttft, tpot = serve_cell.latencies_ms(out)
        res = {"rate": rate, "due": len(out["records"]), "finished": fin,
               "failed": bad, "finished_rps": fin / out["window_s"],
               "out_tok_s": out["tokens"] / out["window_s"],
               "queue_q2": mean(q[quarter:2 * quarter]),
               "queue_q4": mean(q[3 * quarter:]), "queue_max": max(q or [0]),
               "decode_batch_mean": mean([s[5] for s in steps if s[5]]),
               "ttft_ms_p50": arith.percentile(ttft, 50),
               "ttft_ms_p90": arith.percentile(ttft, 90),
               "tpot_ms_p50": arith.percentile(tpot, 50),
               "tpot_ms_p90": arith.percentile(tpot, 90)}
        res["sustained"] = sustained(res)
        if res["sustained"]:
            best = rate if best is None else max(best, rate)
        print(json.dumps({"sweep": res}), flush=True)
        eng.run()                      # drain before the next rate
    print(json.dumps({"knee": best, "cell_rate": None if best is None
                      else round(0.8 * best, 3)}), flush=True)
    eng.shutdown()


if __name__ == "__main__":
    main()
