#!/usr/bin/env python
"""Continuous-batching serving benchmark: aggregate tokens/sec, TTFT,
and preemption behavior of ``mxnet_tpu.serve.Engine`` under load.

The serving-side companion to tools/decode_bench.py (single-stream
decode): builds a checkpoint-shaped random GPT, replays a mixed
prompt-length workload through the engine, and reports the numbers a
serving operator tunes for — aggregate tokens/sec, mean/max
time-to-first-token, preemptions/evictions under cache pressure, and
the speedup over serial single-request decode of the SAME workload
(the continuous-batching win itself).

Two load modes:

  closed  at most --concurrency requests in flight; a completion
          immediately admits the next (throughput-oriented).
  open    Poisson arrivals at --rate req/s; admission-queue overflow
          is counted as back-pressure rejection, never a silent drop
          (latency/SLO-oriented).

Emits the same last-line JSON + ``--json`` artifact contract as the
other bench tools (tools/bench_io.py).

Usage: python tools/serve_bench.py [--backend cpu] [--json OUT]
           [--requests 32 --concurrency 8 --prompt-lens 16,32,64,128]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_workload(rng, args):
    """(prompt, max_new) pairs cycling the mixed prompt lengths."""
    lens = [int(x) for x in args.prompt_lens.split(",")]
    work = []
    for i in range(args.requests):
        n = lens[i % len(lens)]
        work.append((rng.randint(0, args.vocab, (n,)).astype("int32"),
                     args.max_new))
    return work


def build_shared_prefix_workload(rng, args):
    """The prefix-cache workload: ``--prefixes`` distinct system
    prompts x ``--continuations`` short unique suffixes each,
    interleaved prefix-major so the first wave is exactly one cold
    prefill per prefix and everything after can hit the cache."""
    import numpy as np

    prefixes = [rng.randint(0, args.vocab,
                            (args.prefix_len,)).astype("int32")
                for _ in range(args.prefixes)]
    work = []
    for _ in range(args.continuations):
        for p in prefixes:
            sfx = rng.randint(0, args.vocab,
                              (args.suffix_len,)).astype("int32")
            work.append((np.concatenate([p, sfx]), args.max_new))
    return work


def build_offload_workload(rng, args):
    """The host-KV-offload workload: ``--offload-prefixes`` distinct
    system prompts x ``--continuations`` suffixes, prefix-major rounds
    — each round touches EVERY prefix once, so an HBM prefix LRU sized
    for only a couple of chains re-misses on-chip every round and must
    either recompute the prefix (offload off) or restore it from DRAM
    (offload on)."""
    import numpy as np

    prefixes = [rng.randint(0, args.vocab,
                            (args.prefix_len,)).astype("int32")
                for _ in range(args.offload_prefixes)]
    work = []
    for _ in range(args.continuations):
        for p in prefixes:
            sfx = rng.randint(0, args.vocab,
                              (args.suffix_len,)).astype("int32")
            work.append((np.concatenate([p, sfx]), args.max_new))
    return work


def run_offload(mx, args, make_engine, workload):
    """Host-RAM KV offload A/B over an HBM prefix cache sized to
    thrash: offload-on vs offload-off on the SAME small cache, plus an
    unconstrained-HBM reference (the hit rate the tier should recover)
    and a cache-off cold baseline.  Int8-KV and tp=2 arms rerun the
    offload-on/off pair under those modes.  The acceptance bars: hit
    rate recovered to >= 0.8 of unconstrained, >= 2x less prefill
    compute than offload-off, tokens byte-identical in every arm."""
    import jax

    conc = 1     # sequential: each request sees its predecessors'
    #              evictions deterministically — the thrash is the test
    sp_len = args.prefix_len + args.suffix_len + args.max_new
    bf = mx.serve.kv_block_manager.blocks_for
    chain = bf(args.prefix_len, args.block_size)
    # thrashing HBM: the live request plus ~2 chains' worth of LRU —
    # by round 2 every prefix has been pushed out on-chip, so the A/B
    # isolates what the DRAM tier recovers
    small = 1 + 2 * chain + bf(sp_len + 1, args.block_size) + 1
    # every request's full published chain (prefix + suffix + decode
    # tail) stays resident — the reference arm must never evict
    big = 1 + (len(workload) + 2) * bf(sp_len + 1, args.block_size)
    # DRAM budget covering every chain with headroom (the tier's whole
    # point: DRAM is orders of magnitude larger than the HBM cache)
    host_bytes = 1 << 30
    kw = dict(max_model_len=sp_len, max_queue=len(workload) + 1)

    # warm both program families (the restore family exists — and
    # fingerprints — only with the tier on)
    for wkw in (dict(num_blocks=big),
                dict(num_blocks=small, host_kv_bytes=host_bytes)):
        weng = make_engine(conc, **dict(kw, **wkw))
        weng.warmup()
        weng.shutdown()

    def once(num_blocks, **ekw):
        eng = make_engine(conc, num_blocks=num_blocks, **dict(kw, **ekw))
        reqs, wall = run_closed(mx, eng, workload, conc)
        st = eng.stats()
        hk = eng.host_kv_stats()
        eng.shutdown()
        return reqs, wall, st, hk

    cold_reqs, _, cold_st, _ = once(big, prefix_cache=False)
    ref_reqs, _, ref_st, _ = once(big)                 # unconstrained
    off_reqs, off_wall, off_st, _ = once(small)        # thrash, no tier
    on_reqs, on_wall, on_st, on_hk = once(small, host_kv_bytes=host_bytes)

    def identical(a, b):
        return all(x.status == y.status == "finished"
                   and x.tokens == y.tokens for x, y in zip(a, b))

    idents = {"off_vs_cold": identical(off_reqs, cold_reqs),
              "on_vs_cold": identical(on_reqs, cold_reqs),
              "ref_vs_cold": identical(ref_reqs, cold_reqs)}

    # int8-KV arm: quantized cache contents round-trip the host tier
    # (scale slots ride along); identity is WITHIN the int8 pair —
    # int8 legitimately moves tokens vs fp
    i8_off, _, _, _ = once(small, kv_dtype="int8")
    i8_on, _, i8_st, _ = once(small, kv_dtype="int8",
                              host_kv_bytes=host_bytes)
    idents["int8_on_vs_off"] = identical(i8_on, i8_off)

    # tp=2 arm: head-sharded blocks round-trip the host tier per-shard
    # (needs >= 2 devices and tp-divisible heads; skipped otherwise)
    tp2 = None
    if (jax.device_count() >= 2 and args.heads % 2 == 0
            and (args.kv_heads or max(1, args.heads // 4)) % 2 == 0):
        t2_reqs, _, t2_st, _ = once(small, tp=2,
                                    host_kv_bytes=host_bytes)
        idents["tp2_on_vs_cold"] = identical(t2_reqs, cold_reqs)
        tp2 = {"host_kv_hits": t2_st.host_kv_hits,
               "restored_tokens": t2_st.host_kv_restored_tokens}

    ratio = (round(off_st.prefill_tokens_computed
                   / on_st.prefill_tokens_computed, 2)
             if on_st.prefill_tokens_computed else None)
    recovery = (round(on_st.prefix_hit_rate / ref_st.prefix_hit_rate, 4)
                if ref_st.prefix_hit_rate else None)
    return {
        "mode": "offload",
        "requests": len(workload),
        "offload_prefixes": args.offload_prefixes,
        "prefix_len": args.prefix_len,
        "num_blocks_small": small,
        "num_blocks_unconstrained": big,
        "host_kv_bytes": host_bytes,
        "hit_rate_unconstrained": ref_st.prefix_hit_rate,
        "hit_rate_off": off_st.prefix_hit_rate,
        "hit_rate_on": on_st.prefix_hit_rate,
        "hit_rate_recovery": recovery,
        "prefill_tokens_computed_off": off_st.prefill_tokens_computed,
        "prefill_tokens_computed_on": on_st.prefill_tokens_computed,
        "prefill_compute_ratio": ratio,
        "discarded_tokens_off": off_st.prefix_discarded_tokens,
        "discarded_tokens_on": on_st.prefix_discarded_tokens,
        "host_offloads": on_st.host_kv_offloads,
        "host_restores": on_st.host_kv_hits,
        "host_restored_tokens": on_st.host_kv_restored_tokens,
        "host_bytes_peak": (on_hk or {}).get("bytes_peak"),
        "int8_host_kv_hits": i8_st.host_kv_hits,
        "tp2": tp2,
        "tokens_identical": all(idents.values()),
        "identity": idents,
        "wall_s_on": round(on_wall, 3),
        "wall_s_off": round(off_wall, 3),
        "tokens_per_sec_on": (round(sum(len(r.tokens) for r in on_reqs)
                                    / on_wall, 1) if on_wall else None),
        "tokens_per_sec_off": (round(sum(len(r.tokens) for r in off_reqs)
                                     / off_wall, 1) if off_wall else None),
    }


def build_repeat_heavy_workload(rng, args):
    """The spec workload: repeat-heavy prompts — a short random motif
    tiled to each prompt length — cycling the mixed lengths.  Highly
    regular continuations are where a small draft model tracks the
    target best, i.e. the workload class speculative decoding is FOR
    (system-prompt boilerplate, code, templated output)."""
    import numpy as np

    lens = [int(x) for x in args.prompt_lens.split(",")]
    work = []
    for i in range(args.requests):
        n = lens[i % len(lens)]
        motif = rng.randint(0, args.vocab, (max(2, n // 8),))
        prompt = np.tile(motif, -(-n // motif.size))[:n]
        # a short random tail breaks the pure cycle: each request gets
        # its own transient before the continuation settles, so the
        # draft has real chances to be WRONG (a bench where the target
        # never disagrees would leave the rollback path unmeasured)
        tail = max(1, n // 8)
        prompt[-tail:] = rng.randint(0, args.vocab, (tail,))
        work.append((prompt.astype("int32"), args.max_new))
    return work


def distill_family(params, layers, draft_layers, scale=0.05):
    """A target/draft checkpoint pair for the spec A/B: the target is
    ``params`` with every layer >= ``draft_layers`` damped (its proj /
    ff_down residual contributions scaled by ``scale``), the draft is
    the first ``draft_layers`` layers of that SAME checkpoint.  The
    damped target stays a full ``layers``-deep model (every dispatch
    costs full depth); damping just makes the truncation a *plausible*
    draft — the well-distilled-draft situation the feature assumes —
    instead of an uncorrelated one.  Identity never depends on this:
    the A/B reruns the exact damped target spec-off."""
    target = dict(params)
    for k, v in params.items():
        for i in range(draft_layers, layers):
            if k.startswith(f"gpt_l{i}_") and (
                    k.endswith("proj_weight")
                    or k.endswith("ff_down_weight")):
                target[k] = v * scale
    cut = tuple(f"gpt_l{i}_" for i in range(draft_layers, layers))
    draft = {k: v for k, v in target.items() if not k.startswith(cut)}
    return target, draft


def run_spec(mx, args, make_engine, workload, draft):
    """Spec-on vs spec-off over the same repeat-heavy prompts: tok/s
    ratio, acceptance rate — and byte-identical output tokens (the
    acceptance bar).

    Both arms pin ``MXTPU_PAGED_ATTENTION=jnp``: byte identity is a
    PER-FORMULATION contract (the spec-off arm's decode program and
    the spec-on arm's verify program must compute the same logits),
    and on TPU the auto-selected Mosaic decode kernel's online-softmax
    accumulation legitimately differs from the verify program's inline
    math at bf16-logit granularity.  The tok/s ratio this A/B reports
    is therefore jnp-vs-jnp — the honest measurement of the
    ACCEPTANCE algebra, which is what the spec_speedup contract is
    about (the kernel's own win is the quant workload's story)."""
    import os as _os

    conc = args.concurrency
    k = args.spec_k
    prev = _os.environ.get("MXTPU_PAGED_ATTENTION")
    _os.environ["MXTPU_PAGED_ATTENTION"] = "jnp"
    try:
        return _run_spec_pinned(mx, args, make_engine, workload, draft,
                                conc, k)
    finally:
        if prev is None:
            _os.environ.pop("MXTPU_PAGED_ATTENTION", None)
        else:
            _os.environ["MXTPU_PAGED_ATTENTION"] = prev


def _run_spec_pinned(mx, args, make_engine, workload, draft, conc, k):
    blocks_for = mx.serve.kv_block_manager.blocks_for
    max_len = max(len(p) for p, _ in workload) + args.max_new
    # headroom for the verify pass's k+1 transient slots per request
    num_blocks = 1 + (conc + 2) * blocks_for(max_len + k + 1,
                                             args.block_size)
    kw = dict(num_blocks=num_blocks, max_queue=len(workload) + 1)
    spec_kw = dict(spec_k=k, draft_params=draft,
                   draft_num_heads=args.heads, draft_window=0, **kw)

    # warm both program families (spec on/off key the program cache
    # separately: the verify/draft/draft_chunk families only exist —
    # and fingerprint — when spec is on)
    for wkw in (kw, spec_kw):
        weng = make_engine(conc, **wkw)
        weng.warmup()
        weng.shutdown()

    def once(ekw):
        eng = make_engine(conc, **ekw)
        reqs, wall = run_closed(mx, eng, workload, conc)
        st = eng.stats()
        eng.shutdown()
        return reqs, wall, st

    off_reqs, off_wall, off_st = once(kw)
    on_reqs, on_wall, on_st = once(spec_kw)
    identical = all(
        a.status == b.status == "finished" and a.tokens == b.tokens
        for a, b in zip(off_reqs, on_reqs))
    tps_off = (sum(len(r.tokens) for r in off_reqs) / off_wall
               if off_wall else None)
    tps_on = (sum(len(r.tokens) for r in on_reqs) / on_wall
              if on_wall else None)
    return {
        "mode": "spec",
        "requests": len(workload),
        "spec_k": k,
        "draft_layers": args.draft_layers,
        "completed_on": sum(r.status == "finished" for r in on_reqs),
        "completed_off": sum(r.status == "finished" for r in off_reqs),
        "tokens_identical": identical,
        "wall_s_on": round(on_wall, 3),
        "wall_s_off": round(off_wall, 3),
        "tokens_per_sec_on": round(tps_on, 1) if tps_on else None,
        "tokens_per_sec_off": round(tps_off, 1) if tps_off else None,
        "spec_speedup": (round(tps_on / tps_off, 2)
                         if tps_on and tps_off else None),
        "spec_accept_rate": on_st.spec_accept_rate,
        "accepted_per_verify": on_st.accepted_per_verify,
        "spec_verifies": on_st.spec_verifies,
        "spec_drafted_tokens": on_st.spec_drafted_tokens,
        "spec_accepted_tokens": on_st.spec_accepted_tokens,
        "spec_rejected_tokens": on_st.spec_rejected_tokens,
        "decode_occupancy_on": on_st.decode_occupancy,
        "steps_on": on_st.steps,
        "steps_off": off_st.steps,
        "preemptions_on": on_st.preemptions,
    }


SAMPLING_CYCLE = (
    {},                                            # greedy row
    {"temperature": 0.7},
    {"temperature": 1.0, "top_k": 8},
    {"temperature": 0.9, "top_p": 0.8},
    {"temperature": 0.25, "top_k": 16, "logprobs": 2},
)


def sampling_config(i):
    """The mixed-config cycle: request ``i``'s per-request sampling
    kwargs — greedy rows interleaved with distinct temperature /
    top-k / top-p / logprobs asks, all served by ONE bucketed decode
    program (params are operands, not trace keys)."""
    return dict(SAMPLING_CYCLE[i % len(SAMPLING_CYCLE)])


def _two_sample_chisq(a_tokens, b_tokens, min_count=10):
    """Pooled two-sample chi-square over the observed categories
    (rare ones folded into "other").  Returns ``(z, tv, ncat)``:
    the normal-approximated z-score of the statistic vs its df (a
    same-distribution pair sits near 0) and the total-variation
    distance of the two empirical histograms."""
    from collections import Counter

    ca, cb = Counter(a_tokens), Counter(b_tokens)
    cats = [c for c in set(ca) | set(cb)
            if ca.get(c, 0) + cb.get(c, 0) >= min_count]
    other = [c for c in set(ca) | set(cb) if c not in cats]
    na, nb = len(a_tokens), len(b_tokens)
    rows = [(ca.get(c, 0), cb.get(c, 0)) for c in cats]
    if other:
        rows.append((sum(ca.get(c, 0) for c in other),
                     sum(cb.get(c, 0) for c in other)))
    stat = 0.0
    for xa, xb in rows:
        tot = xa + xb
        ea = tot * na / (na + nb)
        eb = tot * nb / (na + nb)
        if ea > 0:
            stat += (xa - ea) ** 2 / ea
        if eb > 0:
            stat += (xb - eb) ** 2 / eb
    df = max(1, len(rows) - 1)
    z = (stat - df) / (2 * df) ** 0.5
    # TV over the SAME pooled categories (raw singleton categories
    # would inflate the empirical TV of two identical distributions)
    tv = 0.5 * sum(abs(xa / na - xb / nb) for xa, xb in rows)
    return round(z, 3), round(tv, 4), len(rows)


def run_sampling(mx, args, make_engine, workload, draft):
    """The sampling workload's three arms (one payload):

    1. mixed-config batch: a warmed sampling-mode engine serves the
       greedy/temperature/top-k/top-p/logprobs cycle — ZERO fresh
       traces (program-cache growth pinned at 0, the operand-vs-
       trace-key contract) and the greedy rows byte-identical to a
       greedy-only engine's output;
    2. spec-on vs spec-off tok/s at temperature > 0 — the rejection-
       sampling acceptance extends the spec speedup to stochastic
       traffic (gate >= 1.25x);
    3. distribution agreement: the (token0, token1) pairs of many
       2-token generations, spec-on vs spec-off, must be two samples
       of ONE distribution (pooled two-sample chi-square z + TV
       distance).

    ``MXTPU_PAGED_ATTENTION=jnp`` pinned for the same per-formulation
    reason as the spec workload."""
    import os as _os

    prev = _os.environ.get("MXTPU_PAGED_ATTENTION")
    _os.environ["MXTPU_PAGED_ATTENTION"] = "jnp"
    try:
        return _run_sampling_pinned(mx, args, make_engine, workload,
                                    draft)
    finally:
        if prev is None:
            _os.environ.pop("MXTPU_PAGED_ATTENTION", None)
        else:
            _os.environ["MXTPU_PAGED_ATTENTION"] = prev


def _run_sampling_pinned(mx, args, make_engine, workload, draft):
    from mxnet_tpu.serve import engine as engine_mod

    blocks_for = mx.serve.kv_block_manager.blocks_for
    conc = args.concurrency
    k = args.spec_k
    temp = args.sampling_temp
    max_len = max(len(p) for p, _ in workload) + args.max_new
    num_blocks = 1 + (conc + 2) * blocks_for(max_len + k + 1,
                                             args.block_size)
    kw = dict(num_blocks=num_blocks, max_queue=len(workload) + 1,
              sampling=True)
    spec_kw = dict(spec_k=k, draft_params=draft,
                   draft_num_heads=args.heads, draft_window=0, **kw)

    # -- arm 1: mixed configs, zero fresh traces, greedy rows exact ----
    geng = make_engine(conc, num_blocks=num_blocks,
                       max_queue=len(workload) + 1)
    g_reqs, _ = run_closed(mx, geng, workload, conc)
    geng.shutdown()
    eng = make_engine(conc, **kw)
    eng.warmup()
    cache_before = len(engine_mod._STEP_CACHE)
    m_reqs, m_wall = run_closed(mx, eng, workload, conc,
                                cfg_fn=sampling_config)
    retraces = len(engine_mod._STEP_CACHE) - cache_before
    greedy_identical = all(
        a.status == b.status == "finished" and a.tokens == b.tokens
        for i, (a, b) in enumerate(zip(g_reqs, m_reqs))
        if not sampling_config(i))
    logprobs_ok = True
    for i, r in enumerate(m_reqs):
        want = sampling_config(i).get("logprobs", 0)
        if not want:
            continue
        if (len(r.token_logprobs) != len(r.tokens)
                or len(r.top_logprobs) != len(r.tokens)
                or any(len(t) != want for t in r.top_logprobs)):
            logprobs_ok = False
    mixed_tps = (sum(len(r.tokens) for r in m_reqs) / m_wall
                 if m_wall else None)
    eng.shutdown()

    # -- arm 2: spec on/off tok/s at temperature > 0 -------------------
    def once(ekw, wl, cfg_fn):
        e = make_engine(conc, **ekw)
        e.warmup()
        rs, wall = run_closed(mx, e, wl, conc, cfg_fn=cfg_fn)
        st = e.stats()
        e.shutdown()
        return rs, wall, st

    stoch = lambda i: {"temperature": temp}   # noqa: E731
    off_reqs, off_wall, off_st = once(kw, workload, stoch)
    on_reqs, on_wall, on_st = once(spec_kw, workload, stoch)
    tps_off = (sum(len(r.tokens) for r in off_reqs) / off_wall
               if off_wall else None)
    tps_on = (sum(len(r.tokens) for r in on_reqs) / on_wall
              if on_wall else None)

    # -- arm 3: distribution agreement, spec-on vs spec-off ------------
    M = args.agreement_samples
    pair_wl = [(workload[0][0], 2)] * M

    def pairs(ekw):
        rs, _, _ = once(ekw, pair_wl, stoch)
        return [(r.tokens[0], r.tokens[1]) for r in rs
                if len(r.tokens) == 2]

    z, tv, ncat = _two_sample_chisq(pairs(kw), pairs(spec_kw))

    return {
        "mode": "sampling",
        "requests": len(workload),
        "spec_k": k,
        "sampling_temp": temp,
        "retraces": retraces,
        "greedy_rows_identical": bool(greedy_identical),
        "logprobs_ok": bool(logprobs_ok),
        "mixed_tokens_per_sec": (round(mixed_tps, 1)
                                 if mixed_tps else None),
        "tokens_per_sec_on": round(tps_on, 1) if tps_on else None,
        "tokens_per_sec_off": round(tps_off, 1) if tps_off else None,
        "sampling_spec_speedup": (round(tps_on / tps_off, 2)
                                  if tps_on and tps_off else None),
        "accept_rate_stochastic": on_st.spec_accept_rate_stochastic,
        "spec_verifies": on_st.spec_verifies,
        "agreement_samples": M,
        "agreement_z": z,
        "agreement_tv": tv,
        "agreement_categories": ncat,
    }


def snap_int8(params, num_heads):
    """Snap every engine-eligible matmul projection onto its
    per-output-channel int8 grid (``w -> dequant(quantize(w))``).
    Weight-only serving of the snapped checkpoint reproduces the fp
    engine (the engine's on-the-fly dequant recovers these values), so
    the quant workload's agreement rates isolate the SERVING-stack
    effects (int8 KV rounding) instead of counting argmax flips on the
    random checkpoint's near-tie logits — ties no trained,
    quantization-friendly model has.  Quantize-then-normalize runs the
    ENGINE's own helpers, so which weights get snapped can never drift
    from which weights the engine quantizes."""
    import numpy as np

    from mxnet_tpu.models.generate import (detect_gpt_variant,
                                           normalize_gpt_params)
    from mxnet_tpu.serve.programs import _quantize_gpt_params

    spec = detect_gpt_variant(params, num_heads)
    snapped = normalize_gpt_params(          # dequants *_wscale (f32)
        _quantize_gpt_params(dict(params), "gpt", spec))
    # back to the checkpoint dtype: a bf16 run must serve a bf16
    # baseline (an f32 snapped weight would widen the baseline's
    # matmuls AND its weight reads, corrupting both sides of the A/B)
    return {k: np.asarray(v).astype(np.asarray(params[k]).dtype)
            if k in params else v for k, v in snapped.items()}


def run_quant(mx, args, make_engine, workload):
    """Quantized-serving A/B/C on the SAME checkpoint: quant-off vs
    weight-only int8 vs weight-only + int8 KV blocks.  Reports tok/s
    ratios, per-chip KV bytes (cache + dequant scales — the honest
    footprint), and the greedy-token agreement rate of each quantized
    variant against the fp baseline (the acceptance gate)."""
    conc = args.concurrency
    kw = dict(max_queue=len(workload) + 1)
    variants = [("off", {}),
                ("weight_only", dict(quantize="int8")),
                ("int8_kv", dict(quantize="int8", kv_dtype="int8"))]

    # warm all three program families (each quant mode keys the
    # program cache and the AOT fingerprints separately)
    for _, vkw in variants:
        weng = make_engine(conc, **dict(kw, **vkw))
        weng.warmup()
        weng.shutdown()

    runs = {}
    for tag, vkw in variants:
        eng = make_engine(conc, **dict(kw, **vkw))
        reqs, wall = run_closed(mx, eng, workload, conc)
        kvs = eng.kv_cache_stats()
        eng.shutdown()
        toks = sum(len(r.tokens) for r in reqs)
        runs[tag] = {
            "reqs": reqs,
            "wall": wall,
            "kv": kvs,
            "tps": round(toks / wall, 1) if wall else None,
            "completed": sum(r.status == "finished" for r in reqs),
        }

    def agreement(tag):
        total = agree = 0
        for a, b in zip(runs["off"]["reqs"], runs[tag]["reqs"]):
            for x, y in zip(a.tokens, b.tokens):
                total += 1
                agree += int(x == y)
        return round(agree / total, 4) if total else None

    def kv_bytes(tag):
        kvs = runs[tag]["kv"]
        return (kvs["bytes_per_device"]
                + kvs.get("scale_bytes_per_device", 0))

    tps_off = runs["off"]["tps"]
    rec = {
        "mode": "quant",
        "requests": len(workload),
        "completed_off": runs["off"]["completed"],
        "completed_weight_only": runs["weight_only"]["completed"],
        "completed_int8_kv": runs["int8_kv"]["completed"],
        "tokens_per_sec_off": tps_off,
        "tokens_per_sec_weight_only": runs["weight_only"]["tps"],
        "tokens_per_sec_int8_kv": runs["int8_kv"]["tps"],
        "weight_only_speedup": (round(runs["weight_only"]["tps"]
                                      / tps_off, 2)
                                if tps_off else None),
        "int8_kv_speedup": (round(runs["int8_kv"]["tps"] / tps_off, 2)
                            if tps_off else None),
        "agreement_weight_only": agreement("weight_only"),
        "agreement_int8_kv": agreement("int8_kv"),
        "kv_bytes_per_device_off": kv_bytes("off"),
        "kv_bytes_per_device_int8": kv_bytes("int8_kv"),
        "kv_bytes_ratio": round(kv_bytes("off") / kv_bytes("int8_kv"),
                                2),
        "kv_cache_dtype_int8": runs["int8_kv"]["kv"]["dtype"],
        "wall_s_off": round(runs["off"]["wall"], 3),
        "wall_s_weight_only": round(runs["weight_only"]["wall"], 3),
        "wall_s_int8_kv": round(runs["int8_kv"]["wall"], 3),
    }
    return rec


def build_lora_family(rng, params, args, k, rank, alpha):
    """``k`` seeded LoRA adapters over every projection stem of the
    bench checkpoint, plus each adapter's merged-weight checkpoint
    (``w + (alpha/r) * B @ A`` — the single-tenant reference engine a
    multiplexed row of that adapter must reproduce)."""
    import numpy as np

    from mxnet_tpu.serve import adapters as adapters_mod

    stems = adapters_mod.gpt_stems("gpt", args.layers, True, True,
                                   params)
    family, merged = {}, {}
    for j in range(k):
        arrays, mp = {}, dict(params)
        for stem, (dout, din) in stems.items():
            a = (rng.randn(rank, din) * 0.1).astype(np.float32)
            b = (rng.randn(dout, rank) * 0.1).astype(np.float32)
            arrays[stem] = (a, b)
            w = np.asarray(mp[f"{stem}_weight"])
            mp[f"{stem}_weight"] = (
                w.astype(np.float32)
                + (alpha / rank) * (b @ a)).astype(w.dtype)
        aid = f"tenant-{j}"
        family[aid] = arrays
        merged[aid] = mp
    return family, merged


def run_lora(mx, args, make_engine, workload, params):
    """Multi-tenant LoRA multiplexing A/B on the SAME checkpoint:

    * **off**: an adapters-off engine over the workload — the baseline
      the multiplexed engine's overhead is measured against (and the
      pay-for-use proof: adapters-off serving is untouched).
    * **mux**: ONE adapters-mode engine serving the same workload with
      rows cycling base + ``--lora-adapters`` adapters, run TWICE with
      the assignment ROTATED between passes — every row switches
      adapter, so the second pass must add ZERO fresh traced programs
      (the slot index is an operand: one program per bucket serves any
      mix) and cannot lean on same-adapter prefix-cache hits.
    * **merged**: per-adapter merged-weight engines re-serving each
      adapter's rows — the single-tenant reference the multiplexed
      rows must agree with (token agreement, not bitwise: the merged
      arm folds the delta into one matmul, the mux arm adds it).
    * **serial**: the merged arms' summed wall — what serving the same
      tenant mix costs as one engine per tenant (the consolidation
      headline: K+1 checkpoints' traffic through one engine's HBM).
    """
    import numpy as np

    import mxnet_tpu.serve.engine as engine_mod

    conc = args.concurrency
    k, rank, alpha = args.lora_adapters, args.lora_rank, 8.0
    rng = np.random.RandomState(args.seed + 7)
    family, merged = build_lora_family(rng, params, args, k, rank,
                                       alpha)
    ids = [None] + sorted(family)

    def assign(i):
        return ids[i % len(ids)]

    def assign2(i):
        # rotated: every row serves a DIFFERENT adapter than pass 1,
        # so pass 2 gets no same-salt prefix-cache hits and a
        # trace-keyed slot would be forced to retrace every bucket
        return ids[(i + 1) % len(ids)]

    kw = dict(max_queue=len(workload) + 1)

    eng = make_engine(conc, **kw)
    # two warm passes: the first traces full-prefill buckets, the
    # second traces the shrunken prefix-cached suffix buckets — the
    # measured pass is then steady-state
    run_closed(mx, eng, workload, conc)
    run_closed(mx, eng, workload, conc)
    off_reqs, off_wall = run_closed(mx, eng, workload, conc)
    eng.shutdown()

    eng = make_engine(conc, adapters=k + 1, adapter_rank=rank, **kw)
    for aid in sorted(family):
        eng.adapter_store.register(aid, family[aid], alpha=alpha)
    cfg = lambda i: ({"adapter_id": assign(i)} if assign(i) else {})
    run_closed(mx, eng, workload, conc, cfg_fn=cfg)   # warm the grid
    progs = len(engine_mod._STEP_CACHE)
    cfg2 = lambda i: ({"adapter_id": assign2(i)} if assign2(i) else {})
    mux_reqs, mux_wall = run_closed(mx, eng, workload, conc,
                                    cfg_fn=cfg2)
    fresh_traces = len(engine_mod._STEP_CACHE) - progs
    adp_stats = eng.adapter_store.stats()
    eng.shutdown()

    total = agree = 0
    serial_wall = 0.0
    for aid in ids:
        rows = [i for i in range(len(workload)) if assign2(i) == aid]
        reng = make_engine(
            conc, params_override=None if aid is None else merged[aid],
            **kw)
        rreqs, rwall = run_closed(mx, reng,
                                  [workload[i] for i in rows], conc)
        reng.shutdown()
        serial_wall += rwall
        for i, rr in zip(rows, rreqs):
            for x, y in zip(rr.tokens, mux_reqs[i].tokens):
                total += 1
                agree += int(x == y)

    mux_toks = sum(len(r.tokens) for r in mux_reqs)
    off_toks = sum(len(r.tokens) for r in off_reqs)
    mux_tps = round(mux_toks / mux_wall, 1) if mux_wall else None
    off_tps = round(off_toks / off_wall, 1) if off_wall else None
    return {
        "mode": "lora",
        "requests": len(workload),
        "adapters": k,
        "adapter_rank": rank,
        "completed_off": sum(r.status == "finished" for r in off_reqs),
        "completed_mux": sum(r.status == "finished" for r in mux_reqs),
        "tokens_per_sec_off": off_tps,
        "tokens_per_sec_mux": mux_tps,
        "mux_overhead_ratio": (round(mux_tps / off_tps, 3)
                               if off_tps and mux_tps else None),
        "fresh_traces_second_pass": fresh_traces,
        "agreement_vs_merged": (round(agree / total, 4)
                                if total else None),
        "tokens_identical": total > 0 and agree == total,
        "wall_s_mux": round(mux_wall, 3),
        "wall_s_serial_merged": round(serial_wall, 3),
        "consolidation_speedup": (round(serial_wall / mux_wall, 2)
                                  if mux_wall else None),
        "adapter_slots_used": adp_stats["slots_used"],
        "adapter_loads": adp_stats["loads"],
    }


def run_perf_attrib(mx, args, make_engine, workload):
    """Performance-attribution A/B over the SAME workload: sampled
    device timing on (every step) vs off.  The acceptance bar: tokens
    byte-identical, the AOT fingerprint unchanged, the sampling
    overhead within measurement noise, and the on-arm's cost table
    populated with nonzero flops for every dispatched family."""
    import os as _os

    from mxnet_tpu.telemetry import perf_attrib as pa

    conc = args.concurrency

    def once(sample_every):
        prev = _os.environ.get(pa.ENV_SAMPLE)
        _os.environ[pa.ENV_SAMPLE] = str(sample_every)
        try:
            eng = make_engine(conc, max_queue=len(workload) + 1)
            reqs, wall = run_closed(mx, eng, workload, conc)
            perf = eng.statusz()["perf"]
            fp = eng._spec_digest
            eng.shutdown()
        finally:
            if prev is None:
                _os.environ.pop(pa.ENV_SAMPLE, None)
            else:
                _os.environ[pa.ENV_SAMPLE] = prev
        return reqs, wall, perf, fp

    # warm the shared program cache AND replay the workload once so
    # neither arm pays compiles or first-touch allocator costs — the
    # overhead_ratio must compare sampling, not run order
    weng = make_engine(conc, max_queue=len(workload) + 1)
    weng.warmup()
    run_closed(mx, weng, workload, conc)
    weng.shutdown()

    off_reqs, off_wall, off_perf, off_fp = once(0)
    on_reqs, on_wall, on_perf, on_fp = once(1)
    identical = all(
        a.status == b.status == "finished" and a.tokens == b.tokens
        for a, b in zip(off_reqs, on_reqs))
    tps_off = (sum(len(r.tokens) for r in off_reqs) / off_wall
               if off_wall else None)
    tps_on = (sum(len(r.tokens) for r in on_reqs) / on_wall
              if on_wall else None)
    rows = on_perf["programs"]
    rec = {
        "mode": "perf-attrib",
        "requests": len(workload),
        "completed_on": sum(r.status == "finished" for r in on_reqs),
        "completed_off": sum(r.status == "finished" for r in off_reqs),
        "tokens_identical": identical,
        "fingerprint_identical": on_fp == off_fp,
        "wall_s_on": round(on_wall, 3),
        "wall_s_off": round(off_wall, 3),
        "tokens_per_sec_on": round(tps_on, 1) if tps_on else None,
        "tokens_per_sec_off": round(tps_off, 1) if tps_off else None,
        # >1 means the sampled sync cost wall time; CI gates this
        # loosely (CPU walls are noisy) — the honest number to track
        "overhead_ratio": (round(on_wall / off_wall, 3)
                           if off_wall else None),
        # the off arm must record ZERO timings (inert default)...
        "off_sampled_steps": off_perf["sampled_steps"],
        # ...while the on arm attributes every step
        "sampled_steps": on_perf["sampled_steps"],
        "sampled_dispatches": sum(r["sampled"] for r in rows),
        "cost_table_kinds": sorted({r["kind"] for r in rows}),
        "cost_flops_nonzero": bool(rows) and all(
            r["flops"] and r["flops"] > 0 for r in rows),
        "cost_errors": on_perf["cost_errors"],
        "achieved_tflops": on_perf["achieved_tflops"],
        "mfu": on_perf["mfu"],
        "tok_flops": on_perf["tok_flops"],
        "cost_per_1k_tokens_s": on_perf["cost_per_1k_tokens_s"],
    }
    return rec


def run_step_profile(mx, args, make_engine, workload):
    """Step-time decomposition A/B over the SAME workload: the
    per-step host-overhead recorder on (default) vs off.  Acceptance:
    tokens byte-identical, the AOT fingerprint unchanged, recorder
    overhead within noise (the committed record gates 1.02x), and the
    on-arm's phase fractions summing to 1 with every phase present."""
    import os as _os

    from mxnet_tpu.telemetry import profiling as sp

    conc = args.concurrency

    def once(enabled):
        prev = _os.environ.get(sp.ENV_ENABLE)
        _os.environ[sp.ENV_ENABLE] = "1" if enabled else "0"
        try:
            eng = make_engine(conc, max_queue=len(workload) + 1)
            reqs, wall = run_closed(mx, eng, workload, conc)
            prof = eng.statusz()["step_profile"]
            fp = eng._spec_digest
            eng.shutdown()
        finally:
            if prev is None:
                _os.environ.pop(sp.ENV_ENABLE, None)
            else:
                _os.environ[sp.ENV_ENABLE] = prev
        return reqs, wall, prof, fp

    # warm the shared program cache AND replay the workload once so
    # neither arm pays compiles or first-touch allocator costs
    weng = make_engine(conc, max_queue=len(workload) + 1)
    weng.warmup()
    run_closed(mx, weng, workload, conc)
    weng.shutdown()

    # interleave the arms and keep each arm's BEST wall: the recorder
    # costs two clock reads per lap — far below run-to-run scheduler
    # jitter on a shared host — so a single off/on pair would gate on
    # noise rather than the recorder
    runs = {False: [], True: []}
    for _ in range(2):
        for enabled in (False, True):
            runs[enabled].append(once(enabled))
    off_reqs, off_wall, off_prof, off_fp = min(
        runs[False], key=lambda r: r[1])
    on_reqs, on_wall, on_prof, on_fp = min(
        runs[True], key=lambda r: r[1])
    ref = runs[False][0][0]
    identical = all(
        a.status == b.status == "finished" and a.tokens == b.tokens
        for arm in runs.values() for r in arm
        for a, b in zip(ref, r[0]))
    tps_off = (sum(len(r.tokens) for r in off_reqs) / off_wall
               if off_wall else None)
    tps_on = (sum(len(r.tokens) for r in on_reqs) / on_wall
              if on_wall else None)
    fr = on_prof.get("fractions") or {}
    rec = {
        "mode": "step-profile",
        "requests": len(workload),
        "completed_on": sum(r.status == "finished" for r in on_reqs),
        "completed_off": sum(r.status == "finished" for r in off_reqs),
        "tokens_identical": identical,
        "fingerprint_identical": on_fp == off_fp,
        "wall_s_on": round(on_wall, 3),
        "wall_s_off": round(off_wall, 3),
        "tokens_per_sec_on": round(tps_on, 1) if tps_on else None,
        "tokens_per_sec_off": round(tps_off, 1) if tps_off else None,
        # >1 means the recorder cost wall time; the committed record
        # must show <= 1.02 (two perf_counter reads per lap)
        "overhead_ratio": (round(on_wall / off_wall, 3)
                           if off_wall else None),
        "tok_s_ratio": (round(tps_on / tps_off, 3)
                        if tps_on and tps_off else None),
        # the off arm must report the NOOP recorder (inert when off)
        "off_enabled": bool(off_prof.get("enabled")),
        "profiled_steps": on_prof.get("steps"),
        "phase_fractions": {k: round(v, 4) for k, v in fr.items()},
        # the lap/cursor model attributes every elapsed nanosecond to
        # exactly one phase, so the fractions sum to 1 by construction
        "fractions_sum": round(sum(fr.values()), 6) if fr else None,
        "phases_all_present": set(fr) == set(sp.PHASES),
    }
    return rec


def run_shared_prefix(mx, args, make_engine, workload):
    """Cache-on vs cache-off over the shared-prefix workload: the
    prefill-compute ratio, hit rate, tokens saved — and byte-identical
    output tokens (the acceptance bar)."""
    # first wave = one cold prefill per distinct prefix: cap the closed
    # loop there so later admissions see the published chains
    conc = min(args.concurrency, args.prefixes)
    sp_len = args.prefix_len + args.suffix_len + args.max_new
    blocks_for = mx.serve.kv_block_manager.blocks_for
    # room for the published prefix chains PLUS conc private suffixes
    # (cache-off needs conc full-length residents, strictly less)
    num_blocks = (1 + args.prefixes * blocks_for(args.prefix_len,
                                                 args.block_size)
                  + (conc + 2) * blocks_for(sp_len + 1, args.block_size))
    kw = dict(max_model_len=sp_len, num_blocks=num_blocks,
              max_queue=len(workload) + 1)

    def once(prefix_cache):
        eng = make_engine(conc, prefix_cache=prefix_cache, **kw)
        reqs, wall = run_closed(mx, eng, workload, conc)
        st = eng.stats()
        eng.shutdown()
        return reqs, wall, st

    weng = make_engine(conc, **kw)
    weng.warmup()                  # dense + chunk + decode buckets
    weng.shutdown()
    off_reqs, off_wall, off_st = once(False)
    on_reqs, on_wall, on_st = once(True)
    identical = all(
        a.status == b.status == "finished" and a.tokens == b.tokens
        for a, b in zip(off_reqs, on_reqs))
    ratio = (round(off_st.prefill_tokens_computed
                   / on_st.prefill_tokens_computed, 2)
             if on_st.prefill_tokens_computed else None)
    return {
        "mode": "shared-prefix",
        "requests": len(workload),
        "prefixes": args.prefixes,
        "continuations": args.continuations,
        "prefix_len": args.prefix_len,
        "suffix_len": args.suffix_len,
        "completed_on": sum(r.status == "finished" for r in on_reqs),
        "completed_off": sum(r.status == "finished" for r in off_reqs),
        "prefix_hit_rate": on_st.prefix_hit_rate,
        "prefix_hits": on_st.prefix_hits,
        "prefix_misses": on_st.prefix_misses,
        "prefill_tokens_saved": on_st.prefix_tokens_saved,
        "prefill_tokens_computed_on": on_st.prefill_tokens_computed,
        "prefill_tokens_computed_off": off_st.prefill_tokens_computed,
        "prefill_compute_ratio": ratio,
        "tokens_identical": identical,
        "wall_s_on": round(on_wall, 3),
        "wall_s_off": round(off_wall, 3),
        "tokens_per_sec_on": (round(sum(len(r.tokens) for r in on_reqs)
                                    / on_wall, 1) if on_wall else None),
        "tokens_per_sec_off": (round(sum(len(r.tokens) for r in off_reqs)
                                     / off_wall, 1) if off_wall else None),
        "preemptions_on": on_st.preemptions,
    }


def run_mixed_len(mx, args, make_engine):
    """One very long prompt amid steadily-decoding short requests:
    chunked prefill vs whole-prompt prefill, reporting the p99
    inter-token latency (decode stall) of the short requests while the
    long prefill is in flight — the chunked-prefill acceptance bar."""
    import numpy as np

    from tools.trace_report import percentile

    rng = np.random.RandomState(args.seed + 1)
    long_len = args.long_prompt
    chunk = args.prefill_chunk or max(32, long_len // 8)
    n_short, short_len, short_new = 4, 16, 96
    short_prompts = [rng.randint(0, args.vocab,
                                 (short_len,)).astype("int32")
                     for _ in range(n_short)]
    long_prompt = rng.randint(0, args.vocab, (long_len,)).astype("int32")
    blocks_for = mx.serve.kv_block_manager.blocks_for
    num_blocks = (2 + blocks_for(long_len + 16, args.block_size)
                  + (n_short + 1) * blocks_for(short_len + short_new + 1,
                                               args.block_size))
    kw = dict(max_model_len=long_len + 16, num_blocks=num_blocks,
              prefix_cache=False, max_queue=n_short + 2)

    weng = make_engine(n_short + 1, prefill_chunk=chunk, **kw)
    weng.warmup()                  # whole-prefill + chunk + decode buckets
    weng.shutdown()

    def once(prefill_chunk):
        eng = make_engine(n_short + 1, prefill_chunk=prefill_chunk, **kw)
        shorts = [eng.submit(p, max_new_tokens=short_new)
                  for p in short_prompts]
        while any(not s.tokens for s in shorts):
            eng.step()             # ramp: every short is decoding
        long_req = eng.submit(long_prompt, max_new_tokens=8)
        last = {s.rid: time.perf_counter() for s in shorts}
        counts = {s.rid: len(s.tokens) for s in shorts}
        gaps = []
        while not long_req.done and eng.scheduler.has_work():
            eng.step()
            now = time.perf_counter()
            for s in shorts:
                if len(s.tokens) > counts[s.rid]:
                    gaps.append(now - last[s.rid])
                    last[s.rid] = now
                    counts[s.rid] = len(s.tokens)
        eng.run()                  # drain the shorts
        st = eng.stats()
        eng.shutdown()
        return long_req, shorts, gaps, st

    long_w, shorts_w, gaps_w, _ = once(0)            # whole-prompt
    long_c, shorts_c, gaps_c, st_c = once(chunk)     # chunked
    identical = (long_w.tokens == long_c.tokens and all(
        a.tokens == b.tokens for a, b in zip(shorts_w, shorts_c)))
    p99_w = percentile(sorted(g * 1e3 for g in gaps_w), 0.99)
    p99_c = percentile(sorted(g * 1e3 for g in gaps_c), 0.99)
    return {
        "mode": "mixed-len",
        "long_prompt": long_len,
        "prefill_chunk": chunk,
        "short_requests": n_short,
        "decode_gaps_whole": len(gaps_w),
        "decode_gaps_chunked": len(gaps_c),
        "decode_stall_p99_ms_whole": round(p99_w, 2),
        "decode_stall_p99_ms_chunked": round(p99_c, 2),
        "decode_stall_max_ms_whole": round(max(gaps_w) * 1e3, 2),
        "decode_stall_max_ms_chunked": round(max(gaps_c) * 1e3, 2),
        "stall_improvement": (round(p99_w / p99_c, 2) if p99_c else None),
        "improved": bool(p99_c < p99_w),
        "tokens_identical": identical,
        "prefill_tokens_computed_chunked": st_c.prefill_tokens_computed,
    }


def run_closed(mx, engine, workload, concurrency, deadline_s=None,
               cfg_fn=None):
    """Closed loop: keep ``concurrency`` requests in flight.  A full
    admission queue throttles the loop (closed-loop clients WAIT for
    capacity — e.g. --max-queue below --concurrency), it never drops.
    ``cfg_fn(i)`` supplies per-request extra submit kwargs (the
    sampling workload's mixed-config cycle)."""
    reqs, inflight, held = [], [], None
    it = iter(enumerate(workload))
    t0 = time.perf_counter()
    while True:
        while len(inflight) < concurrency:
            nxt = held if held is not None else next(it, None)
            if nxt is None:
                break
            held = None
            i, (prompt, max_new) = nxt
            try:
                reqs.append(engine.submit(prompt, max_new_tokens=max_new,
                                          deadline_s=deadline_s,
                                          **(cfg_fn(i) if cfg_fn
                                             else {})))
            except mx.serve.QueueFull:
                held = nxt            # back-pressure: retry after a step
                break
            inflight.append(reqs[-1])
        if not inflight and held is None:
            break
        engine.step()
        inflight = [r for r in inflight if not r.done]
    return reqs, time.perf_counter() - t0


def run_open(mx, engine, workload, rate, rng, deadline_s=None):
    """Open loop: Poisson arrivals at ``rate`` req/s; a full admission
    queue rejects (counted), it never blocks the arrival process."""
    arrivals = rng.exponential(1.0 / rate, len(workload)).cumsum()
    reqs, queue_full = [], 0
    t0 = time.perf_counter()
    i = 0
    while i < len(workload) or engine.scheduler.has_work():
        now = time.perf_counter() - t0
        while i < len(workload) and arrivals[i] <= now:
            prompt, max_new = workload[i]
            try:
                reqs.append(engine.submit(prompt, max_new_tokens=max_new,
                                          deadline_s=deadline_s))
            except mx.serve.QueueFull:
                queue_full += 1
            i += 1
        if engine.scheduler.has_work():
            engine.step()
        elif i < len(workload):
            time.sleep(min(0.005, arrivals[i] - now))
    return reqs, time.perf_counter() - t0, queue_full


def summarize(tag, reqs, wall, stats, n_requests, queue_full=0):
    done = [r for r in reqs if r.status == "finished"]
    rejected = [r for r in reqs if r.status == "rejected"]
    ttfts = [r.ttft() for r in done if r.ttft() is not None]
    toks = sum(len(r.tokens) for r in done)
    rec = {"mode": tag, "requests": n_requests,
           "completed": len(done),
           "rejected": len(rejected) + queue_full,
           "queue_full_rejects": queue_full,
           "dropped_without_rejection":
               n_requests - len(done) - len(rejected) - queue_full,
           "wall_s": round(wall, 3),
           "new_tokens": toks,
           "tokens_per_sec": round(toks / wall, 1) if wall > 0 else None,
           "preemptions": stats.preemptions,
           "evictions": stats.evictions,
           "peak_block_utilization": stats.peak_block_utilization,
           "steps": stats.steps}
    if ttfts:
        ttfts.sort()
        rec["ttft_ms_mean"] = round(sum(ttfts) / len(ttfts) * 1e3, 2)
        rec["ttft_ms_p50"] = round(ttfts[len(ttfts) // 2] * 1e3, 2)
        rec["ttft_ms_max"] = round(ttfts[-1] * 1e3, 2)
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=None,
                   help="default 12 on tpu, 4 off (CPU-tractable smoke)")
    p.add_argument("--d-model", type=int, default=None,
                   help="default 768 on tpu, 256 off")
    p.add_argument("--heads", type=int, default=None,
                   help="default 12 on tpu, 8 off")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA kv heads (default heads//4, min 1)")
    p.add_argument("--vocab", type=int, default=None,
                   help="default 50304 on tpu, 2048 off")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--prompt-lens", default="16,32,64,128")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--mode", default="closed", choices=("closed", "open"))
    p.add_argument("--workload", default="default",
                   choices=("default", "shared-prefix", "mixed-len",
                            "prefix", "spec", "quant", "offload",
                            "sampling", "perf-attrib", "step-profile",
                            "lora"),
                   help="default: the mixed prompt-length load. "
                        "shared-prefix: --prefixes system prompts x "
                        "--continuations suffixes, cache-on vs cache-off "
                        "(prefix-cache acceptance: hit rate, prefill-"
                        "compute ratio, token identity). mixed-len: one "
                        "--long-prompt amid short decoders, chunked vs "
                        "whole-prompt prefill (decode-stall p99 "
                        "acceptance). prefix: both prefix workloads in "
                        "one payload -> the PREFIX_BENCH.json stage. "
                        "spec: speculative decoding on vs off over the "
                        "same repeat-heavy prompts (tok/s ratio, "
                        "acceptance rate, token identity) -> the "
                        "SPEC_BENCH.json stage. "
                        "quant: quant-off vs weight-only int8 vs "
                        "weight-only + int8-KV on the same (int8-"
                        "snapped) checkpoint: tok/s ratios, per-chip "
                        "KV bytes, greedy-token agreement -> the "
                        "QUANT_SERVE_BENCH.json stage. "
                        "offload: host-RAM KV tier A/B over an HBM "
                        "prefix cache sized to thrash — offload-on vs "
                        "off hit rate/prefill compute, vs an "
                        "unconstrained-HBM reference, with int8-KV and "
                        "tp=2 arms, tokens byte-identical everywhere "
                        "-> the OFFLOAD_BENCH.json stage. "
                        "sampling: per-request sampling operands — "
                        "mixed-config batch with zero fresh traces + "
                        "greedy-row identity, spec-on vs spec-off "
                        "tok/s at temperature>0 (rejection-sampling "
                        "acceptance) and a chi-square/TV distribution-"
                        "agreement pin -> the SAMPLING_BENCH.json "
                        "stage. "
                        "perf-attrib: device-timing sampling on vs "
                        "off over the same workload — overhead within "
                        "noise, tokens byte-identical, fingerprints "
                        "unchanged, cost table populated -> the "
                        "PERF_ATTRIB_BENCH.json stage. "
                        "step-profile: the per-step host-overhead "
                        "recorder on vs off over the same workload — "
                        "tokens byte-identical, overhead within "
                        "noise, phase fractions summing to 1 -> the "
                        "PROFILE_BENCH.json stage. "
                        "lora: multi-tenant LoRA multiplexing — one "
                        "adapters-mode engine serving a base + "
                        "--lora-adapters mix (zero fresh traces on "
                        "the second pass) vs an adapters-off "
                        "baseline and per-adapter merged-weight "
                        "reference engines (token agreement + "
                        "consolidation speedup) -> the "
                        "LORA_BENCH.json stage")
    p.add_argument("--offload-prefixes", type=int, default=6,
                   help="offload: distinct system prompts (sized to "
                        "overflow the deliberately small HBM LRU)")
    p.add_argument("--prefixes", type=int, default=4,
                   help="shared-prefix: distinct system prompts")
    p.add_argument("--continuations", type=int, default=6,
                   help="shared-prefix: unique suffixes per prefix")
    p.add_argument("--prefix-len", type=int, default=96,
                   help="shared-prefix: shared system-prompt tokens")
    p.add_argument("--suffix-len", type=int, default=12,
                   help="shared-prefix: unique continuation tokens")
    p.add_argument("--spec-k", type=int, default=4,
                   help="spec: drafted tokens per verify iteration")
    p.add_argument("--draft-layers", type=int, default=1,
                   help="spec: layers kept in the truncated draft "
                        "checkpoint (the target keeps all --layers)")
    p.add_argument("--distill-scale", type=float, default=0.05,
                   help="spec: damping on the target's above-draft "
                        "layers — higher = a worse draft, lower "
                        "acceptance (1.0 = undistilled)")
    p.add_argument("--sampling-temp", type=float, default=0.25,
                   help="sampling: the temperature of the spec A/B "
                        "and agreement arms (>0; low keeps the "
                        "distilled draft's acceptance high)")
    p.add_argument("--agreement-samples", type=int, default=192,
                   help="sampling: 2-token generations per arm of the "
                        "distribution-agreement chi-square")
    p.add_argument("--lora-adapters", type=int, default=3,
                   help="lora: distinct adapters multiplexed alongside "
                        "base-model rows")
    p.add_argument("--lora-rank", type=int, default=4,
                   help="lora: rank of the seeded adapters (and the "
                        "store's padded rank ceiling)")
    p.add_argument("--long-prompt", type=int, default=2048,
                   help="mixed-len: the long prompt's token count")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="mixed-len: chunk size (0 = long-prompt/8)")
    p.add_argument("--rate", type=float, default=16.0,
                   help="open-loop arrival rate, requests/sec")
    p.add_argument("--deadline-s", type=float, default=None)
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel degree: shard params + KV-cache "
                        "over a {'tp': N} mesh. Absent/0 defers to "
                        "MXTPU_SERVE_TP; an explicit --tp 1 forces the "
                        "single-device baseline even when the env var is "
                        "set. On the cpu backend virtual host devices are "
                        "forced so the sharded path benches without a TPU")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=None,
                   help="cache blocks (default: fits ~concurrency+2 "
                        "max-length requests -> real preemption pressure)")
    p.add_argument("--max-queue", type=int, default=None)
    p.add_argument("--no-serial", action="store_true",
                   help="skip the serial single-request baseline")
    p.add_argument("--warmup", type=int, default=1,
                   help="untimed warmup pass to populate the program "
                        "cache (0 to include compiles in the timing)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default=None)
    p.add_argument("--backend", "--platform", dest="platform", default=None)
    args = p.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    try:
        # parsed BEFORE importing mxnet_tpu/jax (tp decides the host
        # virtual-device count, which must be set pre-import); the
        # try/except mirrors base.env_int's malformed-value fallback
        # mxtpu-lint: disable=env-discipline (pre-import parse, cannot
        # touch mxnet_tpu.base yet)
        env_tp = int(os.environ.get("MXTPU_SERVE_TP", "1") or 1)
    except ValueError:
        env_tp = 1
    # an explicit --tp (including --tp 1) beats the deployment env
    # default; only an absent/zero flag defers to MXTPU_SERVE_TP
    eff_tp = args.tp if args.tp else env_tp
    if args.workload == "offload" and eff_tp <= 1:
        # the offload workload's tp=2 arm needs two devices; on the
        # host platform force them BEFORE jax initializes (no-op for a
        # real TPU backend — the flag only affects cpu).  The tp=1
        # arms are unaffected: everything still runs on device 0
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
    if eff_tp > 1:
        # a tp mesh (CLI flag or deployment env default) needs >= tp
        # devices; on the host platform that means forcing virtual
        # devices BEFORE jax initializes (no-op for a real TPU backend
        # — the flag only affects cpu)
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={eff_tp}"
            ).strip()
    import numpy as np

    import mxnet_tpu as mx

    import jax

    from tools.bench_io import make_flush
    from tools.decode_bench import make_params

    on_tpu_now = jax.default_backend() == "tpu"
    # gpt-small-class on chip (decode_bench's config); a CPU run keeps
    # the same serving dynamics on a tractable model
    args.layers = args.layers or (12 if on_tpu_now else 4)
    args.d_model = args.d_model or (768 if on_tpu_now else 256)
    args.heads = args.heads or (12 if on_tpu_now else 8)
    args.vocab = args.vocab or (50304 if on_tpu_now else 2048)

    lens = [int(x) for x in args.prompt_lens.split(",")]
    max_len = max(lens) + args.max_new
    # the prefix workloads size the model themselves: the net must
    # cover whatever max_model_len their engines will use
    if args.workload in ("shared-prefix", "prefix", "offload"):
        max_len = max(max_len,
                      args.prefix_len + args.suffix_len + args.max_new)
    if args.workload in ("mixed-len", "prefix"):
        max_len = max(max_len, args.long_prompt + 16)
    kv = args.kv_heads or max(1, args.heads // 4)
    if eff_tp > 1 and kv % eff_tp:
        # the head-sharded KV-cache needs kv_heads % tp == 0; bump the
        # GQA default to the mesh width (explicit --kv-heads still wins
        # and may fail loudly in the engine)
        kv = eff_tp if args.kv_heads is None else kv
    S = max_len
    net = mx.models.gpt(args.vocab, S, num_layers=args.layers,
                        d_model=args.d_model, num_heads=args.heads,
                        norm="rmsnorm", mlp="swiglu", pos_embed="rope",
                        tie_embeddings=True, kv_heads=kv)
    on_tpu = jax.default_backend() == "tpu"
    dtype = "bfloat16" if on_tpu else "float32"
    params = make_params(net, 1, S, dtype)
    draft = None
    if args.workload == "quant":
        # the quant A/B serves an int8-snapped checkpoint so agreement
        # measures serving-stack rounding, not random-logit ties
        params = snap_int8(params, args.heads)
    if args.workload in ("spec", "sampling"):
        # the A/B's checkpoint pair: damped target + truncated draft
        # (both engines below serve the SAME damped target, so the
        # identity check compares like with like)
        params, draft = distill_family(params, args.layers,
                                       args.draft_layers,
                                       scale=args.distill_scale)

    blocks_per_req = -(-max_len // args.block_size)
    num_blocks = args.num_blocks or (
        1 + blocks_per_req * (args.concurrency + 2))
    max_queue = args.max_queue or max(args.requests, 2 * args.concurrency)

    tp = args.tp if args.tp else None    # --tp 1 forces single-device

    def make_engine(max_batch, params_override=None, **kw):
        base = dict(block_size=args.block_size, num_blocks=num_blocks,
                    max_batch=max_batch, max_queue=max_queue,
                    max_model_len=max_len, max_prefills_per_step=2, tp=tp)
        base.update(kw)   # the prefix workloads override capacity knobs
        return mx.serve.Engine(
            params if params_override is None else params_override,
            symbol=net, **base)

    out = {"platform": jax.default_backend(),
           "device_kind": getattr(jax.devices()[0], "device_kind", ""),
           "layers": args.layers, "d_model": args.d_model,
           "heads": args.heads, "kv_heads": kv, "vocab": args.vocab,
           "block_size": args.block_size, "num_blocks": num_blocks,
           "concurrency": args.concurrency, "mode": args.mode,
           "workload": args.workload,
           "param_dtype": dtype}
    flush = make_flush(args.json, out)
    pts = []
    out["points"] = pts
    rng = np.random.RandomState(args.seed)

    if args.workload != "default":
        # prefix-cache / chunked-prefill acceptance workloads: each
        # runner is a self-contained cached-vs-cold (or chunked-vs-
        # whole) A/B with its own capacity math; the headline fields
        # land at top level for the serve_prefix contract
        recs = []
        if args.workload in ("shared-prefix", "prefix"):
            wl = build_shared_prefix_workload(rng, args)
            rec = run_shared_prefix(mx, args, make_engine, wl)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            out["prefix_hit_rate"] = rec["prefix_hit_rate"]
            out["prefill_tokens_saved"] = rec["prefill_tokens_saved"]
            out["prefill_compute_ratio"] = rec["prefill_compute_ratio"]
            flush(False)
        if args.workload in ("mixed-len", "prefix"):
            rec = run_mixed_len(mx, args, make_engine)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            out["decode_stall_p99_ms_whole"] = \
                rec["decode_stall_p99_ms_whole"]
            out["decode_stall_p99_ms_chunked"] = \
                rec["decode_stall_p99_ms_chunked"]
            out["stall_improvement"] = rec["stall_improvement"]
            out["stall_improved"] = rec["improved"]
            flush(False)
        if args.workload == "spec":
            wl = build_repeat_heavy_workload(rng, args)
            rec = run_spec(mx, args, make_engine, wl, draft)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            out["spec_k"] = rec["spec_k"]
            out["spec_speedup"] = rec["spec_speedup"]
            out["spec_accept_rate"] = rec["spec_accept_rate"]
            out["accepted_per_verify"] = rec["accepted_per_verify"]
            out["tokens_per_sec_on"] = rec["tokens_per_sec_on"]
            out["tokens_per_sec_off"] = rec["tokens_per_sec_off"]
            flush(False)
        if args.workload == "sampling":
            wl = build_repeat_heavy_workload(rng, args)
            rec = run_sampling(mx, args, make_engine, wl, draft)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_sampling contract fields
            out["retraces"] = rec["retraces"]
            out["greedy_rows_identical"] = rec["greedy_rows_identical"]
            out["logprobs_ok"] = rec["logprobs_ok"]
            out["sampling_spec_speedup"] = rec["sampling_spec_speedup"]
            out["tokens_per_sec_on"] = rec["tokens_per_sec_on"]
            out["tokens_per_sec_off"] = rec["tokens_per_sec_off"]
            out["accept_rate_stochastic"] = rec["accept_rate_stochastic"]
            out["agreement_z"] = rec["agreement_z"]
            out["agreement_tv"] = rec["agreement_tv"]
            out["agreement_samples"] = rec["agreement_samples"]
            flush(False)
        if args.workload == "offload":
            wl = build_offload_workload(rng, args)
            rec = run_offload(mx, args, make_engine, wl)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_offload contract fields
            out["hit_rate_unconstrained"] = rec["hit_rate_unconstrained"]
            out["hit_rate_off"] = rec["hit_rate_off"]
            out["hit_rate_on"] = rec["hit_rate_on"]
            out["hit_rate_recovery"] = rec["hit_rate_recovery"]
            out["prefill_compute_ratio"] = rec["prefill_compute_ratio"]
            out["host_restores"] = rec["host_restores"]
            out["host_restored_tokens"] = rec["host_restored_tokens"]
            out["discarded_tokens_off"] = rec["discarded_tokens_off"]
            flush(False)
        if args.workload == "perf-attrib":
            wl = build_workload(rng, args)
            rec = run_perf_attrib(mx, args, make_engine, wl)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_perf contract fields
            out["fingerprint_identical"] = rec["fingerprint_identical"]
            out["overhead_ratio"] = rec["overhead_ratio"]
            out["sampled_dispatches"] = rec["sampled_dispatches"]
            out["cost_table_kinds"] = rec["cost_table_kinds"]
            out["cost_flops_nonzero"] = rec["cost_flops_nonzero"]
            out["achieved_tflops"] = rec["achieved_tflops"]
            out["mfu"] = rec["mfu"]
            out["tokens_per_sec_on"] = rec["tokens_per_sec_on"]
            out["tokens_per_sec_off"] = rec["tokens_per_sec_off"]
            flush(False)
        if args.workload == "step-profile":
            wl = build_workload(rng, args)
            rec = run_step_profile(mx, args, make_engine, wl)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_step_profile contract fields
            out["fingerprint_identical"] = rec["fingerprint_identical"]
            out["overhead_ratio"] = rec["overhead_ratio"]
            out["tok_s_ratio"] = rec["tok_s_ratio"]
            out["off_enabled"] = rec["off_enabled"]
            out["profiled_steps"] = rec["profiled_steps"]
            out["phase_fractions"] = rec["phase_fractions"]
            out["fractions_sum"] = rec["fractions_sum"]
            out["phases_all_present"] = rec["phases_all_present"]
            out["tokens_per_sec_on"] = rec["tokens_per_sec_on"]
            out["tokens_per_sec_off"] = rec["tokens_per_sec_off"]
            flush(False)
        if args.workload == "lora":
            wl = build_workload(rng, args)
            rec = run_lora(mx, args, make_engine, wl, params)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_lora contract fields: the mixed
            # batch gates on zero fresh traces + agreement vs the
            # merged-weight references (the merged arm folds the delta
            # into one matmul — agreement, not byte identity)
            out["fresh_traces_second_pass"] = \
                rec["fresh_traces_second_pass"]
            out["agreement_vs_merged"] = rec["agreement_vs_merged"]
            out["mux_overhead_ratio"] = rec["mux_overhead_ratio"]
            out["consolidation_speedup"] = rec["consolidation_speedup"]
            out["tokens_per_sec_mux"] = rec["tokens_per_sec_mux"]
            out["lora_adapters"] = rec["adapters"]
            flush(False)
        if args.workload == "quant":
            wl = build_workload(rng, args)
            rec = run_quant(mx, args, make_engine, wl)
            print(json.dumps(rec))
            pts.append(rec)
            recs.append(rec)
            # the serve_quant contract fields: quantized
            # variants gate on AGREEMENT vs the fp baseline (weight
            # rounding legitimately moves tokens), not byte identity
            out["weight_only_speedup"] = rec["weight_only_speedup"]
            out["int8_kv_speedup"] = rec["int8_kv_speedup"]
            out["agreement_weight_only"] = rec["agreement_weight_only"]
            out["agreement_int8_kv"] = rec["agreement_int8_kv"]
            out["kv_bytes_per_device_off"] = \
                rec["kv_bytes_per_device_off"]
            out["kv_bytes_per_device_int8"] = \
                rec["kv_bytes_per_device_int8"]
            out["kv_bytes_ratio"] = rec["kv_bytes_ratio"]
            out["kv_cache_dtype_int8"] = rec["kv_cache_dtype_int8"]
            flush(False)
        idents = [r["tokens_identical"] for r in recs
                  if "tokens_identical" in r]
        if idents:
            out["tokens_identical"] = all(idents)
        out["telemetry"] = mx.telemetry.snapshot()
        flush(True)
        print(json.dumps(out))
        return

    workload = build_workload(rng, args)

    if args.warmup:
        # cover the prompt-length and batch buckets so the measured
        # runs time serving, not XLA compiles: long enough generations
        # that the decode batch actually FILLS (every batch bucket up
        # to the concurrency compiles during ramp-up/drain), plus the
        # half-length prompts preemption-resume prefills would hit.
        # Mid-run preemption can still compile an odd resume-length
        # bucket — acceptable noise.
        # full prompts at the workload's own max_new (anything longer
        # would breach max_model_len and be rejected at submit)
        wl = [(pr, args.max_new) for pr, _ in workload[: args.concurrency]]
        wl += [(pr[: max(1, len(pr) // 2)], min(4, args.max_new))
               for pr, _ in workload[: args.concurrency]]
        eng = make_engine(args.concurrency)
        run_closed(mx, eng, wl, args.concurrency)
        eng.shutdown()
        eng = make_engine(1)
        run_closed(mx, eng, wl[: 2], 1)
        eng.shutdown()

    engine = make_engine(args.concurrency)
    # sharding payload fields come from the measured engine itself —
    # engine.tp, not the CLI flag, so a run sharded via MXTPU_SERVE_TP
    # can never be mislabeled as a tp=1 baseline
    out["tp"] = engine.tp
    out["mesh_shape"] = (dict(engine.mesh.shape)
                         if engine.mesh is not None else None)
    out["kv_bytes_per_device"] = engine.kv_cache_stats()["bytes_per_device"]
    if args.mode == "open":
        reqs, wall, qfull = run_open(mx, engine, workload, args.rate,
                                     rng, args.deadline_s)
    else:
        reqs, wall = run_closed(mx, engine, workload, args.concurrency,
                                args.deadline_s)
        qfull = 0
    stats = engine.stats()
    rec = summarize(f"continuous/{args.mode}", reqs, wall, stats,
                    args.requests, qfull)
    engine.shutdown()
    print(json.dumps(rec))
    pts.append(rec)
    flush(False)

    if not args.no_serial:
        serial = make_engine(1)
        sreqs, swall = run_closed(mx, serial, workload, 1)
        srec = summarize("serial/closed", sreqs, swall, serial.stats(),
                         args.requests)
        serial.shutdown()
        print(json.dumps(srec))
        pts.append(srec)
        if srec.get("tokens_per_sec") and rec.get("tokens_per_sec"):
            out["speedup_vs_serial"] = round(
                rec["tokens_per_sec"] / srec["tokens_per_sec"], 2)

    # headline summary fields (the ARTIFACTS row)
    out["tokens_per_sec"] = rec.get("tokens_per_sec")
    out["ttft_ms_mean"] = rec.get("ttft_ms_mean")
    out["preemptions"] = rec.get("preemptions")
    out["completed"] = rec.get("completed")
    out["rejected"] = rec.get("rejected")
    out["dropped_without_rejection"] = rec.get("dropped_without_rejection")
    # registry snapshot rides along with every record ({"enabled":
    # false, "metrics": {}} unless MXTPU_TELEMETRY=1) — render with
    # tools/metrics_report.py
    out["telemetry"] = mx.telemetry.snapshot()
    flush(True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
