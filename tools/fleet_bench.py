#!/usr/bin/env python
"""Fleet robustness benchmark: availability under chaos + rolling
restart, measured against a real 3-replica process fleet.

Three ``tools/serve_replica.py`` processes (identical weights by
seed), one ``fleet.Supervisor`` (crash restarts), one ``fleet.Router``
(least-loaded + retry-on-sibling).  The run:

  phase 1  open-loop Poisson load through the router while a
           deterministic fault spec (``kill@K``) hard-kills one
           replica mid-stream; the supervisor restarts it.
  phase 2  drain-based rolling restart of ALL replicas under light
           load.

Recorded (FLEET_BENCH.json):

  availability            completed / submitted over phase 1 (the
                          headline: 1.0 means the kill was invisible)
  p99_added_router_ms     p99 of (request wall - time inside replica
                          HTTP calls) — what the router itself costs
  rolling_restart_s       phase 2 wall for all replicas
  slot_restart_s          per-slot drain->ready times
  restart_rejects         client-visible failures during phase 2
                          (contract: 0)
  token_consistent        identical prompts produced identical tokens
                          regardless of which replica served them

Contract (pinned by tests/test_fleet.py's slow-tier case): the payload
stamps ``complete: true`` and ``availability == 1.0`` on the CPU
smoke.  This bench runs the replicas on the CPU backend by design —
N single-host processes cannot share one TPU client, and the
property under test (fault-transparent routing) is backend-agnostic.

``--disagg`` runs the disaggregated prefill/decode A/B instead
(DISAGG_BENCH.json): a
1-prefill + N-decode role-split fleet vs an equal-size role="both"
fleet, same seeded workload — steady decode streams with long prompts
injected mid-run.  Per-replica request traces yield the headline:
**decode-stall p99** (gaps between a running stream's consecutive
decode iterations).  On role="both" replicas an arriving long prompt's
whole-prompt prefill stalls every co-resident stream; on decode-role
replicas prefill work is ~zero (imported KV chains restore from the
handoff, only the final span recomputes), so streams emit a token
every iteration regardless of arriving prompt length.  Also recorded:
handoff wire bytes, dedup hits (content keys the receivers already
cached), availability, and token identity between the two arms.

``--obs`` runs the fleet-observability A/B instead
(FLEET_OBS_BENCH.json): the same
seeded workload through (arm A) a plain fleet and (arm B) a fleet with
the full observability plane live — FleetCollector scraping every
replica, terminal trace lines pushed to its ``/trace``, a lenient
``MXTPU_SLO_SPEC`` evaluated after every scrape — recording
**collector overhead** (tok/s on/off ratio; contract: within noise)
and **SLO attainment** (per-objective bad fractions), with the clean
arm pinned alert-silent.  A third chaos arm (delay + kill faults on
one replica, a tight ``total_p99_ms`` objective, responsive windows)
pins that the burn-rate alert demonstrably FIRES and the flight dump
lands on the offending replica.

``--workload autoscale`` runs the fleet control-plane smoke instead
(AUTOSCALE_BENCH.json): a
role="both" process pool under a live ``fleet.Autoscaler``
(``MXTPU_AUTOSCALE_SPEC`` grammar via ``--autoscale-spec``) and
``fleet.FleetCollector``.  Phase A steps the load up (open-loop burst
past the pool's capacity) and the autoscaler must GROW the pool;
phase B goes quiet and it must SHRINK back to the min bound after the
idle window; phase C rolls a deploy whose new version is armed with a
``kill@2`` fault spec — the canary dies mid-parity-probe and the
``fleet.Deployer`` must auto-roll the fleet back to the old version,
byte-identical on the canary set, while light load keeps flowing
(availability 1.0 across every phase; the router retries around both
the kill and the drains).

``--workload cache-route`` runs the cache-aware-routing A/B
(CACHE_ROUTE_BENCH.json):
the same returning-users order (distinct multi-block prefix per user,
shuffled arrivals) through (arm A) a least-loaded fleet with
``MXTPU_ROUTE_AFFINITY=0`` — the byte-inert baseline — and (arm B) the
cache-aware fleet: replicas advertise radix summaries, the router
scores ``affinity x cached-fraction - load`` and attaches ``kv_pull``
hints, one replica hard-killed mid-run.  Gates: fleet prefix hit rate
at least 2x the baseline's, prefill FLOPs (perf-attribution cost
tables) no higher, availability 1.0 through the kill, tokens
byte-identical across arms, and a directed two-replica pull demo
importing a chain over ``/chain_export`` token-identically.

Usage: python tools/fleet_bench.py [--json OUT] [--replicas 3]
           [--requests 24 --rate 8 --max-new 16 --kill-at 4]
       python tools/fleet_bench.py --disagg [--json OUT]
           [--decode-replicas 2 --decoders 4 --long-prompts 3]
       python tools/fleet_bench.py --obs [--json OUT]
           [--obs-replicas 2 --obs-requests 16]
       python tools/fleet_bench.py --workload autoscale [--json OUT]
           [--autoscale-spec 'both=2:4;up_queue=1.5;down_idle_s=4']
"""

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The orchestrating parent pins ITSELF to the cpu backend before the
# package import: a parent that holds the (single-client) chip starves
# every child that needs it — and the replica children pin cpu
# explicitly anyway (N processes cannot share a chip).
os.environ["JAX_PLATFORMS"] = "cpu"

from mxnet_tpu.fleet import ProcessReplica, Router, Supervisor, \
    probe_health  # noqa: E402
from mxnet_tpu.fleet.supervisor import replica_command  # noqa: E402
# one percentile definition for the whole tool suite: this payload's
# p99 must mean the same thing as a trace_report p99 over the same data
from tools.trace_report import percentile as _percentile  # noqa: E402


def percentile(vals, q):
    return _percentile(sorted(vals), q)


def build_workload(rng, args):
    lens = [int(x) for x in args.prompt_lens.split(",")]
    return [rng.randint(1, args.vocab, size=lens[i % len(lens)]).tolist()
            for i in range(args.requests)]


def run_load(router, workload, rate, max_new, rng, tag):
    """Open loop: Poisson arrivals, one thread per in-flight request.
    Returns (results, failures) keyed by request index."""
    arrivals = []
    t = 0.0
    for _ in workload:
        t += rng.exponential(1.0 / rate)
        arrivals.append(t)
    results, failures = {}, {}
    lock = threading.Lock()

    def one(i, prompt):
        rid = f"{tag}-{i}"
        try:
            res = router.generate(prompt, max_new_tokens=max_new,
                                  request_id=rid,
                                  trace_id=f"{tag}-trace-{i}")
            with lock:
                results[i] = res
        except Exception as e:
            with lock:
                failures[i] = f"{type(e).__name__}: {e}"

    threads = []
    t0 = time.perf_counter()
    for i, prompt in enumerate(workload):
        wait = arrivals[i] - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(i, prompt), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=180)
    return results, failures


def _disagg_workload(args):
    """Deterministic disagg workload: steady decode streams (short
    shared-prefix prompts, long generations) plus long prompts (half
    shared among themselves — handoff dedup fodder) injected mid-run.
    Returns ``(decoders, longs)`` as (prompt, max_new) lists."""
    import numpy as np

    rng = np.random.RandomState(args.seed + 7)
    shared = rng.randint(1, args.vocab, size=8).tolist()
    decoders = [(shared + rng.randint(
        1, args.vocab, size=max(1, args.decoder_len - 8)).tolist(),
        args.decode_new) for _ in range(args.decoders)]
    long_shared = rng.randint(1, args.vocab,
                              size=args.long_len // 2).tolist()
    longs = [(long_shared + rng.randint(
        1, args.vocab, size=args.long_len - len(long_shared)).tolist(),
        args.long_new) for _ in range(args.long_prompts)]
    return decoders, longs


def _decode_stall_gaps(trace_files):
    """Per-request gaps between consecutive decode-iteration trace
    events, pooled across the replicas' request-trace JSONL files —
    the decode-stall distribution (a long prompt monopolizing an
    iteration shows up as one big gap in every co-scheduled stream)."""
    gaps = []
    for path in trace_files:
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                ts = [e["t"] for e in rec.get("events", [])
                      if e.get("ev") == "decode"]
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps


def _run_disagg_arm(args, roles, tag, trace_dir):
    """One fleet arm: spawn ``roles``-shaped replicas, drive the
    workload, scrape the handoff counters, tear down.  Returns the
    arm record (tokens per request, stall gaps, handoff stats)."""
    from mxnet_tpu.fleet import ProcessReplica, Router, Supervisor
    from mxnet_tpu.fleet.supervisor import replica_command
    import urllib.request

    def spawn(slot):
        env = dict(os.environ)
        env.pop("MXTPU_FAULT_SPEC", None)
        env["MXTPU_FLEET_ROLE"] = roles[slot]
        env["MXTPU_REQUEST_TRACE"] = os.path.join(
            trace_dir, f"{tag}-{slot}.jsonl")
        handle = ProcessReplica(
            replica_command(extra_args=[
                "--backend", "cpu", "--seed", str(args.seed),
                "--vocab", str(args.vocab), "--warmup", "full",
                "--max-model-len", str(args.max_model_len),
                "--num-blocks", str(args.num_blocks),
                # a bigger-than-smoke model: the A/B exists to show
                # prefill/decode interference, which needs prefill
                # compute that actually dominates a decode iteration
                "--layers", str(args.model_layers),
                "--d-model", str(args.model_d),
                "--heads", str(args.model_heads),
                "--role", roles[slot]]),
            env=env)
        handle.wait_ready(timeout_s=300)
        return handle

    router = Router([], scrape_interval_s=0.25, timeout_s=60.0,
                    retries=4, backoff_s=0.05, backoff_max_s=0.5,
                    breaker_fails=3, breaker_reset_s=2.0)
    sup = Supervisor(spawn, len(roles), router=router,
                     restart_backoff_s=0.2)
    decoders, longs = _disagg_workload(args)
    results, failures = {}, {}
    lock = threading.Lock()

    def one(idx, prompt, max_new):
        try:
            res = router.generate(prompt, max_new_tokens=max_new,
                                  request_id=f"{tag}-{idx}",
                                  trace_id=f"{tag}-trace-{idx}")
            with lock:
                results[idx] = res
        except Exception as e:
            with lock:
                failures[idx] = f"{type(e).__name__}: {e}"

    handoff = {"received": 0, "exported": 0, "blocks_imported": 0,
               "blocks_deduped": 0, "blocks_rejected": 0,
               "bytes_received": 0, "bytes_exported": 0}
    try:
        sup.start()
        router.scrape()
        router.start()
        sup.run(interval_s=0.25)
        threads = []
        # steady streams first, long prompts injected while they run
        for i, (prompt, max_new) in enumerate(decoders):
            th = threading.Thread(target=one, args=(i, prompt, max_new),
                                  daemon=True)
            th.start()
            threads.append(th)
            time.sleep(0.05)
        time.sleep(args.long_delay)
        for j, (prompt, max_new) in enumerate(longs):
            th = threading.Thread(
                target=one, args=(len(decoders) + j, prompt, max_new),
                daemon=True)
            th.start()
            threads.append(th)
            time.sleep(args.long_gap)
        for th in threads:
            th.join(timeout=300)
        # scrape the per-replica handoff counters before teardown
        for h in sup.handles():
            if h is None or not h.url:
                continue
            try:
                with urllib.request.urlopen(f"{h.url}/statusz.json",
                                            timeout=10) as resp:
                    sec = json.loads(resp.read()).get("replica") or {}
            except (OSError, ValueError):
                continue
            for k in handoff:
                handoff[k] += int((sec.get("handoff") or {}).get(k, 0))
    finally:
        router.stop()
        sup.stop()
    gaps = _decode_stall_gaps(
        [os.path.join(trace_dir, f"{tag}-{s}.jsonl")
         for s in range(len(roles))])
    n = len(decoders) + len(longs)
    return {"roles": roles, "submitted": n, "completed": len(results),
            "availability": round(len(results) / max(1, n), 4),
            "failures": dict(list(failures.items())[:5]),
            "tokens": {i: results[i].tokens for i in results},
            "decode_gaps": len(gaps),
            "decode_stall_p99_ms": (round(1e3 * percentile(gaps, 0.99), 3)
                                    if gaps else None),
            "decode_stall_max_ms": (round(1e3 * max(gaps), 3)
                                    if gaps else None),
            "handoff": handoff}


def run_disagg(args):
    """The --disagg A/B: role-split fleet vs role="both" fleet on one
    seeded workload -> DISAGG_BENCH.json."""
    import tempfile

    out = {"platform": "cpu", "mode": "disagg",
           "decode_replicas": args.decode_replicas,
           "decoders": args.decoders, "decode_new": args.decode_new,
           "long_prompts": args.long_prompts, "long_len": args.long_len,
           "complete": False}

    def flush():
        if args.json:
            tmp = args.json + ".wip"
            with open(tmp, "w") as f:
                f.write(json.dumps(out) + "\n")
            os.replace(tmp, args.json)

    n_replicas = 1 + args.decode_replicas
    with tempfile.TemporaryDirectory(prefix="mxtpu-disagg-") as tdir:
        disagg = _run_disagg_arm(
            args, ["prefill"] + ["decode"] * args.decode_replicas,
            "disagg", tdir)
        out["disagg"] = {k: v for k, v in disagg.items() if k != "tokens"}
        flush()
        both = _run_disagg_arm(args, ["both"] * n_replicas, "both", tdir)
        out["interleaved"] = {k: v for k, v in both.items()
                              if k != "tokens"}
    identical = (set(disagg["tokens"]) == set(both["tokens"])
                 and all(disagg["tokens"][i] == both["tokens"][i]
                         for i in disagg["tokens"]))
    out["tokens_identical"] = identical
    p99_d = disagg["decode_stall_p99_ms"]
    p99_b = both["decode_stall_p99_ms"]
    out["stall_improvement"] = (round(p99_b / p99_d, 2)
                                if p99_d and p99_b else None)
    out["handoff_bytes"] = disagg["handoff"]["bytes_received"]
    out["handoff_dedup_blocks"] = disagg["handoff"]["blocks_deduped"]
    out["complete"] = bool(
        disagg["availability"] == 1.0 and both["availability"] == 1.0
        and identical and disagg["handoff"]["received"] > 0)
    flush()
    print(json.dumps(out))
    return 0 if out["complete"] else 1


def _spawn_obs_replica(args, slot, env_extra):
    """One CPU replica for the obs arms (smoke model, full warmup)."""
    env = dict(os.environ)
    env.pop("MXTPU_FAULT_SPEC", None)
    env.pop("MXTPU_TRACE_PUSH_URL", None)
    env.pop("MXTPU_REQUEST_TRACE", None)
    env.update(env_extra)
    handle = ProcessReplica(
        replica_command(extra_args=[
            "--backend", "cpu", "--seed", str(args.seed),
            "--vocab", str(args.vocab), "--warmup", "full"]),
        env=env)
    handle.wait_ready(timeout_s=240)
    return handle


def _run_obs_arm(args, tag, n_replicas, env_for_slot, collector=None,
                 requests=None, deadline_s=None):
    """Spawn one fleet, drive the seeded workload through a router,
    tear down.  Returns (results, failures, wall_s, tokens_total)."""
    import numpy as np

    router = Router([], scrape_interval_s=0.25, timeout_s=60.0,
                    retries=4, backoff_s=0.05, backoff_max_s=0.5,
                    breaker_fails=5, breaker_reset_s=2.0)
    sup = Supervisor(
        lambda slot: _spawn_obs_replica(args, slot, env_for_slot(slot)),
        n_replicas, router=router, restart_backoff_s=0.2,
        collector=collector)
    if collector is not None:
        collector.router = router
    rng = np.random.RandomState(args.seed)
    workload = build_workload(rng, argparse.Namespace(
        prompt_lens=args.prompt_lens, vocab=args.vocab,
        requests=requests if requests is not None else args.obs_requests))
    try:
        sup.start()
        router.scrape()
        router.start()
        sup.run(interval_s=0.25)
        if collector is not None:
            collector.scrape()
            collector.start()
        t0 = time.perf_counter()
        results, failures = run_load(
            router, workload, args.obs_rate, args.max_new,
            np.random.RandomState(args.seed + 3), tag)
        wall = time.perf_counter() - t0
        if collector is not None:
            time.sleep(0.6)          # let the last trace pushes land
            collector.scrape()       # final aggregate + SLO pass
    finally:
        if collector is not None:
            collector.stop()
        router.stop()
        sup.stop()
    tokens = sum(len(r.tokens) for r in results.values())
    return results, failures, wall, tokens


def run_obs(args):
    """The --obs A/B/chaos run -> FLEET_OBS_BENCH.json."""
    import tempfile

    from mxnet_tpu.fleet import FleetCollector, SLOEvaluator, \
        parse_slo_spec

    out = {"platform": "cpu", "mode": "obs",
           "replicas": args.obs_replicas,
           "requests": args.obs_requests, "complete": False}

    def flush():
        if args.json:
            tmp = args.json + ".wip"
            with open(tmp, "w") as f:
                f.write(json.dumps(out) + "\n")
            os.replace(tmp, args.json)

    with tempfile.TemporaryDirectory(prefix="mxtpu-obs-") as tdir:
        # -- arm A: plain fleet, no observability plane -------------------
        res_a, fail_a, wall_a, tok_a = _run_obs_arm(
            args, "off", args.obs_replicas, lambda slot: {})
        out["off"] = {"completed": len(res_a), "failures": len(fail_a),
                      "wall_s": round(wall_a, 3), "tokens": tok_a,
                      "tok_per_sec": round(tok_a / wall_a, 2)}
        flush()

        # -- arm B: collector + trace push + lenient SLOs (clean) ---------
        col = FleetCollector(urls=[], interval_s=0.25, port=0,
                             slo_spec="")
        col.slo = SLOEvaluator(
            parse_slo_spec(args.obs_slo_clean), col,
            fast_s=10.0, slow_s=30.0, fast_burn=10.0, slow_burn=5.0,
            min_requests=5)
        col.start()                      # endpoint up before replicas

        def env_on(slot):
            return {"MXTPU_REQUEST_TRACE":
                    os.path.join(tdir, f"on-{slot}.jsonl"),
                    "MXTPU_TRACE_PUSH_URL": col.url + "/trace"}

        res_b, fail_b, wall_b, tok_b = _run_obs_arm(
            args, "on", args.obs_replicas, env_on, collector=col)
        view = col.fleet_view()
        fired_clean = any(o["fired_total"]
                          for o in view["slo"]["objectives"])
        out["on"] = {"completed": len(res_b), "failures": len(fail_b),
                     "wall_s": round(wall_b, 3), "tokens": tok_b,
                     "tok_per_sec": round(tok_b / wall_b, 2),
                     "traces_received": view["traces"]["received"],
                     "scrape_passes": view["scrape_passes"],
                     "totals": view["totals"]}
        out["slo_attainment"] = {
            o["objective"]: {"bad_slow": o.get("bad_slow"),
                             "total_slow": o.get("total_slow"),
                             "burn_slow": o.get("burn_slow")}
            for o in view["slo"]["objectives"]}
        out["alert_fired_clean"] = bool(fired_clean)
        out["overhead_ratio"] = round(
            out["on"]["tok_per_sec"] / out["off"]["tok_per_sec"], 3)
        # three-view spot check: fleet totals vs summed router results
        out["fleet_tokens_agree"] = (
            view["totals"]["tokens_generated"] >= tok_b)
        flush()

        # -- arm C: chaos — delay+kill on slot 1, tight SLO, must FIRE ----
        chaos_dir = os.path.join(tdir, "flight")
        col_c = FleetCollector(urls=[], interval_s=0.25, port=0,
                               slo_spec="")
        col_c.slo = SLOEvaluator(
            parse_slo_spec(f"total_p{args.obs_chaos_pct}_ms="
                           f"{args.obs_chaos_target_ms}"),
            col_c, fast_s=15.0, slow_s=45.0, fast_burn=1.5,
            slow_burn=1.0, min_requests=4, dump_interval_s=0.0)
        col_c.start()
        delays = ";".join(f"delay@{k}:{args.obs_chaos_delay}"
                          for k in range(1, 8))

        def env_chaos(slot):
            env = {"MXTPU_REQUEST_TRACE":
                   os.path.join(tdir, f"chaos-{slot}.jsonl"),
                   "MXTPU_TRACE_PUSH_URL": col_c.url + "/trace",
                   "MXTPU_FLIGHT_DIR": chaos_dir}
            if slot == 1:
                env["MXTPU_FAULT_SPEC"] = delays + ";kill@8"
            return env

        # the ROUTER's trace line is the one that sees client-visible
        # latency (the delay fault sleeps before the engine ever sees
        # the request, so engine-side totals stay clean) — trace the
        # bench parent's router into the same collector
        os.environ["MXTPU_TRACE_PUSH_URL"] = col_c.url + "/trace"
        try:
            res_c, fail_c, wall_c, tok_c = _run_obs_arm(
                args, "chaos", args.obs_replicas, env_chaos,
                collector=col_c, requests=args.obs_requests)
        finally:
            os.environ.pop("MXTPU_TRACE_PUSH_URL", None)
        view_c = col_c.fleet_view()
        fired_chaos = any(o["fired_total"]
                          for o in view_c["slo"]["objectives"])
        dumps = sorted(
            f for f in (os.listdir(chaos_dir)
                        if os.path.isdir(chaos_dir) else [])
            if f.startswith("flight-") and "slo_burn" in f)
        out["chaos"] = {"completed": len(res_c),
                        "failures": len(fail_c),
                        "kill_spec": delays + ";kill@8",
                        "traces_received":
                            view_c["traces"]["received"],
                        "slo": view_c["slo"]["objectives"],
                        "annotations": [
                            a for a in view_c["annotations"]
                            if a["kind"].startswith("slo")]}
        out["alert_fired_chaos"] = bool(fired_chaos)
        out["chaos_flight_dumps"] = len(dumps)
    out["complete"] = bool(
        len(res_a) == len(res_b)
        and not fail_a and not fail_b
        and not out["alert_fired_clean"]
        and out["alert_fired_chaos"]
        and out["chaos_flight_dumps"] > 0
        and out["overhead_ratio"] >= args.obs_overhead_floor)
    flush()
    print(json.dumps(out))
    return 0 if out["complete"] else 1


def run_autoscale(args):
    """The --workload autoscale control-plane smoke ->
    AUTOSCALE_BENCH.json: step load up (autoscaler grows the pool),
    go quiet (it shrinks to the min bound), then roll a deploy whose
    kill-armed canary forces an automatic token-identical rollback
    under light load."""
    import tempfile

    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.fleet import (Autoscaler, Deployer, FleetCollector,
                                 parse_autoscale_spec)

    spec = parse_autoscale_spec(args.autoscale_spec)
    lo, hi = spec["bounds"]["both"]
    out = {"platform": "cpu", "mode": "autoscale",
           "spec": args.autoscale_spec, "min_replicas": lo,
           "max_replicas_bound": hi, "complete": False,
           "scaled_up": False, "scaled_down": False,
           "rollback_token_identical": False}

    def flush():
        if args.json:
            tmp = args.json + ".wip"
            with open(tmp, "w") as f:
                f.write(json.dumps(out) + "\n")
            os.replace(tmp, args.json)

    def make_spawn(version, seed, fault=None):
        """A version-tagged spawn factory — the deploy arm passes a
        second one as the 'new checkpoint' (same weights iff same
        seed) with an optional fault spec armed on its replicas."""
        def spawn(slot):
            env = dict(os.environ)
            env.pop("MXTPU_FAULT_SPEC", None)
            # the parent's flight dir is for the CONTROL PLANE's
            # actuation dumps; children must not write into the count
            env.pop("MXTPU_FLIGHT_DIR", None)
            if fault:
                env["MXTPU_FAULT_SPEC"] = fault
            handle = ProcessReplica(
                replica_command(extra_args=[
                    "--backend", "cpu", "--seed", str(seed),
                    "--vocab", str(args.vocab), "--warmup", "full",
                    "--version", version]),
                env=env)
            handle.wait_ready(timeout_s=240)
            return handle
        return spawn

    telemetry.enable()              # the parent hosts the control
    # plane, so its registry carries the scale/deploy counters
    router = Router([], scrape_interval_s=0.25, timeout_s=60.0,
                    retries=4, backoff_s=0.05, backoff_max_s=0.5,
                    breaker_fails=5, breaker_reset_s=2.0)
    col = FleetCollector(urls=[], interval_s=0.3, port=0, slo_spec="")
    sup = Supervisor(make_spawn("v1", args.seed), lo, router=router,
                     restart_backoff_s=0.2, collector=col)
    col.router = router
    scaler = Autoscaler(col, sup, spec=args.autoscale_spec,
                        interval_s=0.5)
    deployer = Deployer(sup, collector=col)
    rng = np.random.RandomState(args.seed)
    t_start = time.perf_counter()
    tdir = tempfile.TemporaryDirectory(prefix="mxtpu-autoscale-")
    flight_dir = os.path.join(tdir.name, "flight")
    os.environ["MXTPU_FLIGHT_DIR"] = flight_dir
    try:
        sup.start()
        out["fleet_ready_s"] = round(time.perf_counter() - t_start, 3)
        router.scrape()
        router.start()
        sup.run(interval_s=0.25)
        col.scrape()
        col.start()
        scaler.start()
        flush()

        # -- phase A: step load up -> the pool must GROW ------------------
        workload = build_workload(rng, argparse.Namespace(
            prompt_lens=args.prompt_lens, vocab=args.vocab,
            requests=args.scale_requests))
        hi_results, hi_failures = {}, {}
        burst_done = threading.Event()

        def burst():
            res, fail = run_load(
                router, workload, args.scale_rate, args.max_new,
                np.random.RandomState(args.seed + 3), "burst")
            hi_results.update(res)
            hi_failures.update(fail)
            burst_done.set()

        threading.Thread(target=burst, daemon=True).start()
        peak = sup.pool_size()
        deadline = time.monotonic() + 180
        grace_end = None            # set when the burst finishes
        while time.monotonic() < deadline:
            peak = max(peak, sup.pool_size())
            if burst_done.is_set():
                if peak > lo:
                    break
                if grace_end is None:
                    # the burst drained before a scale-up landed: give
                    # the (slow, spawn-bound) actuation a beat to show
                    grace_end = time.monotonic() + 20
                elif time.monotonic() > grace_end:
                    break
            time.sleep(0.1)
        burst_done.wait(timeout=300)
        peak = max(peak, sup.pool_size())
        out["peak_replicas"] = peak
        out["scaled_up"] = peak > lo
        out["burst_submitted"] = len(workload)
        out["burst_completed"] = len(hi_results)
        out["burst_failures"] = dict(list(hi_failures.items())[:5])
        flush()

        # -- phase B: quiet -> the pool must SHRINK to the min bound ------
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and sup.pool_size() > lo:
            time.sleep(0.2)
        out["settled_replicas"] = sup.pool_size()
        out["scaled_down"] = (out["scaled_up"]
                              and sup.pool_size() == lo)
        scaler.stop()               # the deploy phase owns the pool now
        snap = telemetry.registry().snapshot().get(
            "mxtpu_fleet_scale_events_total") or {}
        out["scale_events"] = [
            {"labels": s["labels"], "value": s["value"]}
            for s in snap.get("samples", [])]
        flush()

        # -- phase C: rolling deploy, canary killed mid-probe -------------
        ref_url = None
        for slot in sup.active_slots():
            h = sup.handles()[slot]
            if h is not None and h.url:
                ref_url = h.url
                break
        ref = deployer.probe(ref_url, "both")
        light = build_workload(rng, argparse.Namespace(
            prompt_lens=args.prompt_lens, vocab=args.vocab,
            requests=args.rollout_requests))
        lo_results, lo_failures = {}, {}
        light_done = threading.Event()

        def light_load():
            res, fail = run_load(
                router, light, args.rollout_rate, args.max_new,
                np.random.RandomState(args.seed + 5), "deploy")
            lo_results.update(res)
            lo_failures.update(fail)
            light_done.set()

        threading.Thread(target=light_load, daemon=True).start()
        time.sleep(0.3)             # the rollout lands MID-load
        # the "new checkpoint" is a different seed (parity must fail
        # even if a routed request burns the kill arrival first) armed
        # to die on its 2nd /generate — the canary probe or a routed
        # request kills it mid-rollout either way
        report = deployer.rollout(
            make_spawn("v2", args.seed + 1, fault="kill@2"),
            version="v2")
        light_done.wait(timeout=300)
        out["rollout"] = {k: report[k] for k in
                          ("status", "reason", "replaced",
                           "rolled_back")}
        out["deploy_submitted"] = len(light)
        out["deploy_completed"] = len(lo_results)
        out["restart_rejects"] = len(lo_failures)
        out["deploy_failures"] = dict(list(lo_failures.items())[:5])

        # the rollback must have restored the OLD weights everywhere:
        # every surviving replica re-serves the canary byte-identically
        identical = report["status"] == "rolled_back"
        versions = set()
        for slot in sup.active_slots():
            h = sup.handles()[slot]
            if h is None or not h.url:
                identical = False
                continue
            try:
                identical = identical and deployer.probe(
                    h.url, "both") == ref
            except (OSError, ValueError):
                identical = False
            hz = probe_health(h.url)
            versions.add((hz or {}).get("version"))
        out["rollback_token_identical"] = bool(identical)
        out["post_rollback_versions"] = sorted(
            v for v in versions if v)
        out["crash_restarts"] = int(sum(sup._restarts))
        out["flight_dumps"] = len(
            [f for f in (os.listdir(flight_dir)
                         if os.path.isdir(flight_dir) else [])
             if f.startswith("flight-")])
        out["annotations"] = [
            {"kind": a["kind"],
             **{k: a[k] for k in ("role", "direction", "reason",
                                  "status", "phase") if k in a}}
            for a in col.fleet_view().get("annotations", ())
            if a["kind"].startswith(("autoscale", "deploy",
                                     "scale_"))][-40:]
        submitted = len(workload) + len(light)
        completed = len(hi_results) + len(lo_results)
        out["availability"] = round(completed / max(1, submitted), 4)
        out["complete"] = bool(
            out["availability"] == 1.0
            and not hi_failures and not lo_failures
            and out["scaled_up"] and out["scaled_down"]
            and report["status"] == "rolled_back"
            and out["rollback_token_identical"]
            and out["post_rollback_versions"] == ["v1"])
    finally:
        os.environ.pop("MXTPU_FLIGHT_DIR", None)
        scaler.stop()
        col.stop()
        router.stop()
        sup.stop()
        tdir.cleanup()
    flush()
    print(json.dumps(out))
    return 0 if out["complete"] else 1


def _cache_route_order(args):
    """Returning-users workload: ``route_users`` users, each with a
    distinct multi-block prefix, each sending one request per round
    with a fresh suffix.  Per-round arrival order is shuffled (fixed
    seed) so a least-loaded router's round-robin tiebreak cannot
    accidentally pin a user to one replica — the baseline arm must
    earn its hit rate, not inherit it from arrival phase.  Returns the
    flat [(user, prompt), ...] list BOTH arms replay identically."""
    import numpy as np

    rng = np.random.RandomState(args.seed + 11)
    prefixes = [rng.randint(1, args.vocab,
                            size=args.route_prefix_len).tolist()
                for _ in range(args.route_users)]
    order = []
    for _ in range(args.route_rounds):
        users = list(range(args.route_users))
        rng.shuffle(users)
        for u in users:
            suffix = rng.randint(1, args.vocab,
                                 size=args.route_suffix_len).tolist()
            order.append((u, prefixes[u] + suffix))
    return prefixes, order


def _scrape_route_stats(handles):
    """Sum prefix-cache / pull counters and prefill FLOPs across the
    fleet's /statusz.json snapshots; also returns the per-replica rows
    the payload keeps for attribution."""
    import urllib.request

    agg = {"prefix_hits": 0, "prefix_misses": 0,
           "prefix_resurrections": 0, "prefix_tokens_saved": 0,
           "prefill_tokens_computed": 0, "prefill_flops": 0,
           "pull_attempts": 0, "pull_blocks_imported": 0,
           "pull_blocks_rejected": 0, "pull_false_positives": 0,
           "pull_failures": 0, "chain_exports": 0}
    rows = []
    for h in handles:
        if h is None or not h.url:
            continue
        try:
            with urllib.request.urlopen(f"{h.url}/statusz.json",
                                        timeout=10) as resp:
                snap = json.loads(resp.read())
        except (OSError, ValueError):
            continue
        sec = snap.get("replica") or {}
        stats = sec.get("stats") or {}
        pull = sec.get("pull") or {}
        summary = sec.get("kv_summary") or {}
        # per-program cost table (PR's perf-attribution plane): the
        # prefill FLOPs the arm actually dispatched — the compute the
        # cache-aware arm exists to not spend
        flops = 0
        for name, section in snap.items():
            if not (isinstance(section, dict)
                    and name.startswith("serve")):
                continue
            for prog in (section.get("perf") or {}).get("programs", []):
                if "prefill" in str(prog.get("kind", "")) \
                        and prog.get("flops"):
                    flops += int(prog["flops"]) * int(
                        prog.get("dispatches") or 0)
        row = {"replica": sec.get("replica"),
               "prefix_hits": int(stats.get("prefix_hits") or 0),
               "prefix_misses": int(stats.get("prefix_misses") or 0),
               "prefix_resurrections":
                   int(stats.get("prefix_resurrections") or 0),
               "prefix_tokens_saved":
                   int(stats.get("prefix_tokens_saved") or 0),
               "prefill_tokens_computed":
                   int(stats.get("prefill_tokens_computed") or 0),
               "prefill_flops": flops,
               "summary_keys": int(summary.get("keys") or 0),
               "pull": {k: int(v) for k, v in pull.items()}}
        rows.append(row)
        for k in ("prefix_hits", "prefix_misses",
                  "prefix_resurrections", "prefix_tokens_saved",
                  "prefill_tokens_computed", "prefill_flops"):
            agg[k] += row[k]
        for k, v in pull.items():
            if f"pull_{k}" in agg:
                agg[f"pull_{k}"] += int(v)
        agg["chain_exports"] += int(pull.get("chain_exports") or 0)
    hm = agg["prefix_hits"] + agg["prefix_misses"]
    agg["fleet_hit_rate"] = (round(agg["prefix_hits"] / hm, 4)
                             if hm else None)
    return agg, rows


def _run_cache_route_arm(args, tag, order, affinity, kill_at=0):
    """One cache-route arm: a role='both' fleet with the host-KV tier
    on, the shared returning-users order driven round by round (a beat
    between rounds lets the router's scrape pick up fresh summaries),
    prefix/pull counters scraped before teardown."""
    spec_armed = {1: False}

    def spawn(slot):
        env = dict(os.environ)
        env.pop("MXTPU_FAULT_SPEC", None)
        if slot == 1 and kill_at and not spec_armed[1]:
            # first life only: the crash-restart replacement (cache
            # cold — exactly what the pull path exists for) must come
            # back clean
            spec_armed[1] = True
            env["MXTPU_FAULT_SPEC"] = f"kill@{kill_at}"
        handle = ProcessReplica(
            replica_command(extra_args=[
                "--backend", "cpu", "--seed", str(args.seed),
                "--vocab", str(args.vocab), "--warmup", "full",
                "--num-blocks", str(args.route_num_blocks),
                "--host-kv-bytes", str(args.route_host_kv_bytes)]),
            env=env)
        handle.wait_ready(timeout_s=240)
        return handle

    router = Router([], scrape_interval_s=0.2, timeout_s=60.0,
                    retries=4, backoff_s=0.05, backoff_max_s=0.5,
                    breaker_fails=3, breaker_reset_s=2.0,
                    affinity=affinity, pull=affinity > 0)
    sup = Supervisor(spawn, args.route_replicas, router=router,
                     restart_backoff_s=0.2)
    results, failures = {}, {}
    lock = threading.Lock()

    def one(idx, prompt):
        try:
            res = router.generate(prompt,
                                  max_new_tokens=args.route_new,
                                  request_id=f"{tag}-{idx}",
                                  trace_id=f"{tag}-trace-{idx}")
            with lock:
                results[idx] = res
        except Exception as e:
            with lock:
                failures[idx] = f"{type(e).__name__}: {e}"

    try:
        sup.start()
        router.scrape()
        router.start()
        sup.run(interval_s=0.25)
        per_round = args.route_users
        for start in range(0, len(order), per_round):
            threads = []
            for idx in range(start, min(start + per_round, len(order))):
                th = threading.Thread(target=one,
                                      args=(idx, order[idx][1]),
                                      daemon=True)
                th.start()
                threads.append(th)
                time.sleep(0.02)
            for th in threads:
                th.join(timeout=180)
            # two scrape periods: published blocks must reach the
            # router's summary view before the users come back
            time.sleep(0.5)
        agg, rows = _scrape_route_stats(sup.handles())
        urls = [h.url for h in sup.handles()
                if h is not None and h.url]
    finally:
        router.stop()
        sup.stop()
    n = len(order)
    return {"affinity": affinity, "submitted": n,
            "completed": len(results),
            "availability": round(len(results) / max(1, n), 4),
            "failures": dict(list(failures.items())[:5]),
            "tokens": {i: results[i].tokens for i in results},
            "replica_of": {i: results[i].replica for i in results},
            "retried_requests": sum(1 for r in results.values()
                                    if r.attempts > 1),
            "stats": agg, "replicas": rows, "urls": urls}


def _cache_route_pull_demo(args, prefixes):
    """Directed p2p-pull check: serve one user's prompt on replica A
    (publishing its chain), then hand replica B the same prompt WITH a
    ``kv_pull`` hint naming A — B must import the chain over
    /chain_export (sha1 + chain-hash verified) and produce the exact
    tokens A produces.  Returns the payload section."""
    import urllib.request

    import numpy as np

    def spawn(slot):
        env = dict(os.environ)
        env.pop("MXTPU_FAULT_SPEC", None)
        handle = ProcessReplica(
            replica_command(extra_args=[
                "--backend", "cpu", "--seed", str(args.seed),
                "--vocab", str(args.vocab), "--warmup", "full",
                "--num-blocks", str(args.route_num_blocks),
                "--host-kv-bytes", str(args.route_host_kv_bytes)]),
            env=env)
        handle.wait_ready(timeout_s=240)
        return handle

    rng = np.random.RandomState(args.seed + 13)
    prompt = prefixes[0] + rng.randint(
        1, args.vocab, size=args.route_suffix_len).tolist()

    def gen(url, body):
        req = urllib.request.Request(
            f"{url}/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    sup = Supervisor(spawn, 2)
    try:
        sup.start()
        a, b = (h.url for h in sup.handles())
        warm = gen(a, {"prompt": prompt,
                       "max_new_tokens": args.route_new,
                       "request_id": "pull-demo-warm"})
        pulled = gen(b, {"prompt": prompt,
                         "max_new_tokens": args.route_new,
                         "request_id": "pull-demo-cold",
                         "kv_pull": {"peer": a,
                                     "tokens": args.route_prefix_len}})
        with urllib.request.urlopen(f"{b}/statusz.json",
                                    timeout=10) as resp:
            pull = (json.loads(resp.read()).get("replica")
                    or {}).get("pull") or {}
    finally:
        sup.stop()
    return {"tokens_identical": warm["tokens"] == pulled["tokens"],
            "blocks_imported": int(pull.get("blocks_imported") or 0),
            "blocks_rejected": int(pull.get("blocks_rejected") or 0),
            "bytes_received": int(pull.get("bytes_received") or 0),
            "failures": int(pull.get("failures") or 0)}


def run_cache_route(args):
    """The --workload cache-route A/B -> CACHE_ROUTE_BENCH.json: the
    same returning-users order through a least-loaded fleet
    (affinity=0, the byte-inert baseline) and a cache-aware fleet
    (affinity routing + p2p pull) with one mid-run replica kill — the
    cache-aware arm must at least double the fleet prefix hit rate,
    spend fewer prefill FLOPs, keep availability 1.0 through the kill,
    and produce byte-identical tokens."""
    prefixes, order = _cache_route_order(args)
    out = {"platform": "cpu", "mode": "cache-route",
           "replicas": args.route_replicas,
           "users": args.route_users, "rounds": args.route_rounds,
           "prefix_len": args.route_prefix_len,
           "suffix_len": args.route_suffix_len,
           "requests": len(order),
           "kill_spec": (f"kill@{args.route_kill_at}"
                         if args.route_kill_at else None),
           "complete": False}

    def flush():
        if args.json:
            tmp = args.json + ".wip"
            with open(tmp, "w") as f:
                f.write(json.dumps(out) + "\n")
            os.replace(tmp, args.json)

    flush()
    base = _run_cache_route_arm(args, "route-base", order, affinity=0.0)
    out["baseline"] = {k: v for k, v in base.items()
                       if k not in ("tokens", "replica_of", "urls")}
    flush()
    aff = _run_cache_route_arm(args, "route-aff", order,
                               affinity=args.route_affinity,
                               kill_at=args.route_kill_at)
    out["affinity"] = {k: v for k, v in aff.items()
                       if k not in ("tokens", "replica_of", "urls")}
    identical = (set(base["tokens"]) == set(aff["tokens"])
                 and all(base["tokens"][i] == aff["tokens"][i]
                         for i in base["tokens"]))
    out["tokens_identical"] = identical
    hr_b = base["stats"]["fleet_hit_rate"] or 0.0
    hr_a = aff["stats"]["fleet_hit_rate"] or 0.0
    out["hit_rate_baseline"] = hr_b
    out["hit_rate_affinity"] = hr_a
    out["hit_rate_improvement"] = (round(hr_a / hr_b, 2) if hr_b
                                   else None)
    fb = base["stats"]["prefill_flops"]
    fa = aff["stats"]["prefill_flops"]
    out["prefill_flops_baseline"] = fb
    out["prefill_flops_affinity"] = fa
    out["prefill_flops_ratio"] = round(fa / fb, 4) if fb else None
    out["prefill_tokens_computed_baseline"] = \
        base["stats"]["prefill_tokens_computed"]
    out["prefill_tokens_computed_affinity"] = \
        aff["stats"]["prefill_tokens_computed"]
    out["pull_demo"] = _cache_route_pull_demo(args, prefixes)
    out["complete"] = bool(
        base["availability"] == 1.0 and aff["availability"] == 1.0
        and identical
        and hr_b > 0 and hr_a >= 2.0 * hr_b
        and out["prefill_tokens_computed_affinity"]
        <= out["prefill_tokens_computed_baseline"]
        and out["pull_demo"]["tokens_identical"]
        and out["pull_demo"]["blocks_imported"] > 0
        and out["pull_demo"]["failures"] == 0)
    flush()
    print(json.dumps(out))
    return 0 if out["complete"] else 1


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--rate", type=float, default=8.0,
                   help="open-loop arrival rate, requests/sec")
    p.add_argument("--prompt-lens", default="8,12,16")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--kill-at", type=int, default=4,
                   help="fault spec kill@K armed on replica slot 1's "
                        "first life (0 disables the chaos phase)")
    p.add_argument("--restart-requests", type=int, default=12,
                   help="light-load requests during the rolling restart")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default=None)
    # -- disaggregated prefill/decode A/B (DISAGG_BENCH.json) ----------
    p.add_argument("--disagg", action="store_true",
                   help="run the role-split vs role='both' A/B instead "
                        "of the chaos/rolling-restart phases")
    p.add_argument("--decode-replicas", type=int, default=2,
                   help="decode-role replicas beside the 1 prefill "
                        "replica (the 'both' arm matches the total)")
    p.add_argument("--decoders", type=int, default=4,
                   help="steady decode streams running when the long "
                        "prompts arrive")
    p.add_argument("--decoder-len", type=int, default=16)
    p.add_argument("--decode-new", type=int, default=100,
                   help="tokens each steady stream generates (long "
                        "enough to outlive the long-prompt injections)")
    p.add_argument("--long-prompts", type=int, default=6)
    p.add_argument("--long-len", type=int, default=800,
                   help="long-prompt length: dense prefill is O(n^2), "
                        "so this sets how hard an arrival stalls an "
                        "interleaved replica's decode batch")
    p.add_argument("--long-new", type=int, default=8)
    p.add_argument("--long-delay", type=float, default=0.1,
                   help="seconds the streams decode before the first "
                        "long prompt arrives")
    p.add_argument("--long-gap", type=float, default=0.08,
                   help="seconds between long-prompt arrivals")
    p.add_argument("--max-model-len", type=int, default=896)
    p.add_argument("--num-blocks", type=int, default=768)
    p.add_argument("--model-layers", type=int, default=4)
    p.add_argument("--model-d", type=int, default=256)
    p.add_argument("--model-heads", type=int, default=8)
    # -- fleet observability A/B (FLEET_OBS_BENCH.json) ----------------
    p.add_argument("--obs", action="store_true",
                   help="run the collector-on vs collector-off A/B "
                        "plus the SLO chaos arm instead")
    p.add_argument("--obs-replicas", type=int, default=2)
    p.add_argument("--obs-requests", type=int, default=16)
    p.add_argument("--obs-rate", type=float, default=6.0,
                   help="open-loop arrival rate of the obs arms")
    p.add_argument("--obs-slo-clean", default="availability=0.5;"
                   "total_p99_ms=60000",
                   help="lenient objectives for the clean arm (the "
                        "alert must stay silent)")
    p.add_argument("--obs-chaos-pct", default="90",
                   help="percentile of the chaos arm's total-latency "
                        "objective")
    p.add_argument("--obs-chaos-target-ms", type=float, default=400.0,
                   help="chaos-arm latency target — the injected "
                        "delays push most requests past it")
    p.add_argument("--obs-chaos-delay", type=float, default=1.0,
                   help="seconds each delay fault sleeps")
    p.add_argument("--obs-overhead-floor", type=float, default=0.75,
                   help="min tok/s ratio (collector-on / off) the "
                        "contract accepts — CPU smoke noise is large")
    # -- fleet control plane smoke (AUTOSCALE_BENCH.json) --------------
    p.add_argument("--workload", default=None,
                   choices=["autoscale", "cache-route"],
                   help="'autoscale' runs the control-plane smoke "
                        "(autoscaler grow/shrink + kill-armed deploy "
                        "rollback) instead; 'cache-route' runs the "
                        "cache-aware-routing A/B (affinity + p2p pull "
                        "vs least-loaded) -> CACHE_ROUTE_BENCH.json")
    p.add_argument("--autoscale-spec",
                   default="both=2:4;up_queue=1.5;down_idle_s=4;"
                           "cooldown_s=2",
                   help="the MXTPU_AUTOSCALE_SPEC grammar driving the "
                        "arm's Autoscaler (bounds + thresholds)")
    p.add_argument("--scale-requests", type=int, default=32,
                   help="burst requests of the step-up phase")
    p.add_argument("--scale-rate", type=float, default=24.0,
                   help="burst arrival rate — past the min pool's "
                        "capacity so queue pressure builds")
    p.add_argument("--rollout-requests", type=int, default=8,
                   help="light-load requests riding the deploy phase")
    p.add_argument("--rollout-rate", type=float, default=2.0)
    # -- cache-aware routing A/B (CACHE_ROUTE_BENCH.json) --------------
    p.add_argument("--route-replicas", type=int, default=4)
    p.add_argument("--route-users", type=int, default=8,
                   help="returning users, each owning one multi-block "
                        "prefix the affinity router should pin")
    p.add_argument("--route-rounds", type=int, default=6,
                   help="times each user comes back (round 1 is cold)")
    p.add_argument("--route-prefix-len", type=int, default=48,
                   help="per-user shared-prefix tokens (must span "
                        "several KV blocks to exercise the chain)")
    p.add_argument("--route-suffix-len", type=int, default=8,
                   help="fresh per-request suffix tokens")
    p.add_argument("--route-new", type=int, default=8)
    p.add_argument("--route-affinity", type=float, default=1.0,
                   help="MXTPU_ROUTE_AFFINITY weight of the cache-"
                        "aware arm (the baseline arm always runs 0)")
    p.add_argument("--route-kill-at", type=int, default=3,
                   help="kill@K armed on slot 1's first life in the "
                        "cache-aware arm (0 disables the chaos)")
    p.add_argument("--route-num-blocks", type=int, default=24,
                   help="device KV blocks per replica — sized so only "
                        "~2 users' chains stay cached: the baseline "
                        "arm churns the LRU while the affinity arm's "
                        "pinning retains (an uncapacitated cache lets "
                        "every replica eventually hold every prefix, "
                        "which flatters the least-loaded baseline)")
    p.add_argument("--route-host-kv-bytes", type=int, default=16 << 10,
                   help="host-DRAM KV tier per replica — the landing "
                        "zone for pulled chains; kept as tight as the "
                        "device tier so it cannot quietly hold the "
                        "whole working set either")
    args = p.parse_args()

    if args.disagg:
        return run_disagg(args)
    if args.obs:
        return run_obs(args)
    if args.workload == "autoscale":
        return run_autoscale(args)
    if args.workload == "cache-route":
        return run_cache_route(args)

    import numpy as np

    rng = np.random.RandomState(args.seed)
    out = {"platform": "cpu", "replicas": args.replicas,
           "requests": args.requests, "rate": args.rate,
           "max_new": args.max_new,
           "kill_spec": (f"kill@{args.kill_at}" if args.kill_at else None),
           "complete": False}

    def flush():
        if args.json:
            tmp = args.json + ".wip"
            with open(tmp, "w") as f:
                f.write(json.dumps(out) + "\n")
            os.replace(tmp, args.json)

    spec_armed = {1: False}

    def spawn(slot):
        env = dict(os.environ)
        env.pop("MXTPU_FAULT_SPEC", None)
        if slot == 1 and args.kill_at and not spec_armed[1]:
            # only the FIRST life of slot 1 carries the kill — its
            # crash-restart replacement must come back healthy
            spec_armed[1] = True
            env["MXTPU_FAULT_SPEC"] = f"kill@{args.kill_at}"
        handle = ProcessReplica(
            replica_command(extra_args=[
                "--backend", "cpu", "--seed", str(args.seed),
                "--vocab", str(args.vocab), "--warmup", "full",
                "--exit-on-drained"]),
            env=env)
        handle.wait_ready(timeout_s=240)
        return handle

    router = Router([], scrape_interval_s=0.25, timeout_s=60.0,
                    retries=4, backoff_s=0.05, backoff_max_s=0.5,
                    breaker_fails=3, breaker_reset_s=2.0)
    sup = Supervisor(spawn, args.replicas, router=router,
                     restart_backoff_s=0.2)
    t_start = time.perf_counter()
    # startup INSIDE the try: a slot that fails wait_ready mid-start
    # must still tear down the replicas already spawned (sup.stop()
    # terminates every handle in the slots list) instead of orphaning
    # them
    try:
        sup.start()
        out["fleet_ready_s"] = round(time.perf_counter() - t_start, 3)
        router.scrape()
        router.start()
        sup.run(interval_s=0.25)
        flush()
        # -- phase 1: chaos load ------------------------------------------
        workload = build_workload(rng, args)
        t1 = time.perf_counter()
        results, failures = run_load(router, workload, args.rate,
                                     args.max_new, rng, "chaos")
        wall = time.perf_counter() - t1
        completed = len(results)
        out["submitted"] = len(workload)
        out["completed"] = completed
        out["failures"] = dict(list(failures.items())[:5])
        out["availability"] = round(completed / max(1, len(workload)), 4)
        out["wall_s"] = round(wall, 3)
        out["retried_requests"] = sum(
            1 for r in results.values() if r.attempts > 1)
        out["p99_added_router_ms"] = (
            round(1e3 * percentile(
                [r.added_s for r in results.values()], 0.99), 3)
            if results else None)
        out["p50_request_ms"] = (
            round(1e3 * percentile(
                [r.wall_s for r in results.values()], 0.50), 3)
            if results else None)
        # identical prompts must yield identical tokens, whichever
        # replica (or retry path) served them
        by_prompt = {}
        consistent = True
        for i, res in results.items():
            key = tuple(workload[i])
            prev = by_prompt.setdefault(key, res.tokens)
            consistent = consistent and (prev == res.tokens)
        out["token_consistent"] = consistent
        out["replicas_used"] = sorted(
            {r.replica for r in results.values()})
        out["crash_restarts"] = int(sum(sup._restarts))
        flush()

        # -- phase 2: rolling restart under light load --------------------
        light = build_workload(
            rng, argparse.Namespace(
                prompt_lens=args.prompt_lens, vocab=args.vocab,
                requests=args.restart_requests))
        r_results, r_failures = {}, {}
        load_done = threading.Event()

        def light_load():
            res, fail = run_load(
                router, light, max(2.0, args.rate / 2), args.max_new,
                np.random.RandomState(args.seed + 1), "restart")
            r_results.update(res)
            r_failures.update(fail)
            load_done.set()

        lt = threading.Thread(target=light_load, daemon=True)
        t2 = time.perf_counter()
        slot_times = []
        lt.start()
        for slot in range(args.replicas):
            s0 = time.perf_counter()
            sup.drain_and_restart(slot)
            slot_times.append(round(time.perf_counter() - s0, 3))
        out["rolling_restart_s"] = round(time.perf_counter() - t2, 3)
        out["slot_restart_s"] = slot_times
        load_done.wait(timeout=300)
        out["restart_submitted"] = len(light)
        out["restart_completed"] = len(r_results)
        out["restart_rejects"] = len(r_failures)
        out["complete"] = bool(
            completed == len(workload) and not failures
            and len(r_results) == len(light) and not r_failures
            and consistent)
    finally:
        router.stop()
        sup.stop()
    flush()
    print(json.dumps(out))
    return 0 if out["complete"] else 1


if __name__ == "__main__":
    sys.exit(main())
