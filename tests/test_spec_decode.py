"""Draft-model speculative decoding (mxnet_tpu/serve/spec.py).

The parity suite for multi-token verified decode: spec-on output must
be byte-identical to plain one-token decode (greedy acceptance makes
the target's argmax decide every emitted token — the draft only
decides how many arrive per dispatch), for both the gpt2-style and
llama-style/GQA variants, and that identity must survive
preemption-by-recomputation, prefix-cache reuse, eviction pressure
and the max_model_len boundary.  Alongside identity: the KV
tail-truncation rollback (never frees a shared/refcounted block,
regression-pinned), the k=0 inert path (same programs, same AOT
fingerprints as a pre-spec engine), acceptance-rate stats agreement
across ServeStats / statusz / the telemetry registry, the
low-acceptance flight-recorder anomaly, per-iteration `emitted` token
counts in request traces (and trace_report's run-length math), and
the verify/draft program families in the AOT warmup grid with a
zero-fresh-trace warm restart.

Everything is CPU-deterministic on tiny models; the measured spec-on
vs spec-off throughput contract lives in test_bench_contract.py (slow
tier) against tools/serve_bench.py --workload spec.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serve import BlockManager, spec as spec_mod
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.telemetry import flight

# the serve-family test modules share one vocab so their plain-decode
# programs are _STEP_CACHE-compatible across modules (the spec-enabled
# programs key separately on spec_k + draft config)
VOCAB = 53


# -- KV tail truncation (bare BlockManager, the rollback primitive) ----------
def test_truncate_releases_only_the_tail():
    m = BlockManager(num_blocks=16, block_size=4)
    t = m.allocate("a", 14)                        # 4 blocks
    assert m.truncate("a", 6) == 2                 # keep 2, free 2
    assert m.table("a") == t[:2]
    assert all(b in m._free for b in t[2:])
    # idempotent / bounded: nothing left beyond the keep point
    assert m.truncate("a", 6) == 0
    assert m.truncate("missing", 1) == 0           # unknown rid: no-op
    # a request always keeps at least one block
    assert m.truncate("a", 0) == 1
    assert len(m.table("a")) == 1


def test_truncate_never_frees_a_shared_block():
    """The regression pin: truncation stops at the first block another
    live table still references — a speculative rollback can never
    free (or even decref) a shared prefix-cache block."""
    m = BlockManager(num_blocks=16, block_size=4)
    ids = list(range(10, 22))                      # 3 full blocks
    t1, _ = m.allocate("a", 13, token_ids=ids)
    m.note_tokens("a", ids)
    t2, c2 = m.allocate("b", 13, token_ids=ids)    # shares 2 blocks
    assert c2 == 8
    # truncating b below the shared span must stop AT the share
    assert m.truncate("b", 1) >= 1                 # b's private tail goes
    for blk in t2[:2]:                             # shared head intact...
        assert m._refs[blk] == 2                   # ...refcounts untouched
        assert blk not in m._free
    assert m.table("a") == t1                      # a never perturbed


def test_truncate_trims_published_chain():
    """A truncated table's published chain entry can never extend past
    the table (a later prefix hit must not resurrect freed blocks)."""
    m = BlockManager(num_blocks=16, block_size=4)
    ids = list(range(30, 42))
    m.allocate("a", 13, token_ids=ids)
    m.note_tokens("a", ids)
    m.truncate("a", 5)                             # keep 2 blocks
    assert len(m._chain.get("a", [])) <= len(m.table("a"))
    m.free("a", retain=True)
    # probing the full prompt hits at most the kept span
    blocks, tokens = m.prefix_probe(ids)
    assert tokens <= 8


# -- engine fixtures (same recipe as test_prefix_cache) ----------------------
@pytest.fixture(scope="module")
def model():
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4)
    return net, _rand_params(net, S, seed=3)


@pytest.fixture(scope="module")
def llama_model():
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4,
                        kv_heads=2, norm="rmsnorm", mlp="swiglu",
                        pos_embed="rope", tie_embeddings=True)
    return net, _rand_params(net, S, seed=9)


def _rand_params(net, S, seed):
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 0.35 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale
                        + (1.0 if name.endswith("gamma") else 0.0)
                        ).astype(np.float32)
    return params


def _draft_of(params, damp=None):
    """A 1-layer truncated draft of a 2-layer checkpoint.  With
    ``damp`` set, the TARGET's layer-1 residual contributions are
    scaled down first (the distilled-family trick from serve_bench:
    the truncation becomes a plausible draft instead of an
    uncorrelated one) — returns (target, draft)."""
    src = dict(params)
    if damp is not None:
        for k, v in params.items():
            if k.startswith("gpt_l1_") and (k.endswith("proj_weight")
                                            or k.endswith("ff_down_weight")):
                src[k] = v * damp
    return src, {k: v for k, v in src.items()
                 if not k.startswith("gpt_l1_")}


def _engine(model, params=None, **kw):
    net, p = model
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefills_per_step", 2)
    return mx.serve.Engine(params if params is not None else p,
                           symbol=net, **kw)


def _spec_kw(draft, k=3):
    return dict(spec_k=k, draft_params=draft, draft_num_heads=4,
                draft_window=0)


def _prompts(ns=(7, 12, 5, 9), seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (n,)).astype(np.int32) for n in ns]


def _serve(eng, prompts, max_new=12):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    return reqs


def _identity(model, spec_engine_kw, plain_engine_kw=None, prompts=None,
              max_new=12, params=None):
    """Serve the same prompts spec-off and spec-on; assert byte
    identity and a non-vacuous verify count.  Returns the spec
    engine's final stats."""
    prompts = _prompts() if prompts is None else prompts
    ref_eng = _engine(model, params=params, **(plain_engine_kw or {}))
    refs = _serve(ref_eng, prompts, max_new)
    ref_eng.shutdown()

    eng = _engine(model, params=params, **spec_engine_kw)
    got = _serve(eng, prompts, max_new)
    st = eng.stats()
    eng.shutdown()
    assert st.spec_verifies > 0, "no verify passes — test is vacuous"
    for a, b in zip(refs, got):
        assert a.status == b.status == "finished"
        assert a.tokens == b.tokens
    return st


# -- byte-identity acceptance gates ------------------------------------------
def test_spec_vs_plain_identity_gpt(model):
    """Acceptance: spec-on output byte-identical to spec-off
    (gpt2-style variant, an untuned draft — acceptance is low, the
    rollback path runs constantly)."""
    _, draft = _draft_of(model[1])
    st = _identity(model, _spec_kw(draft))
    assert st.spec_drafted_tokens == (st.spec_accepted_tokens
                                      + st.spec_rejected_tokens)
    assert st.spec_rejected_tokens > 0       # rollback actually exercised


def test_spec_vs_plain_identity_llama_gqa(llama_model):
    """Same gate on the llama-style variant (rope position offsets in
    the verify rows, GQA grouped gather) with a DISTILLED draft — high
    acceptance, multi-token emits per iteration."""
    target, draft = _draft_of(llama_model[1], damp=0.05)
    st = _identity(llama_model, _spec_kw(draft, k=4), params=target)
    assert st.accepted_per_verify > 1.0      # the draft actually earns


def test_spec_identity_under_preemption(llama_model):
    """Resume-equivalence with spec on: preemption-by-recomputation
    must re-ingest the draft cache and keep emitting exactly the
    plain-decode stream."""
    target, draft = _draft_of(llama_model[1], damp=0.05)
    prompts = _prompts(ns=(12, 9, 14, 7, 11, 8), seed=21)
    ref_eng = _engine(llama_model, params=target, num_blocks=64)
    refs = _serve(ref_eng, prompts, max_new=16)
    ref_eng.shutdown()

    eng = _engine(llama_model, params=target, num_blocks=22,
                  **_spec_kw(draft, k=4))
    got = _serve(eng, prompts, max_new=16)
    st = eng.stats()
    eng.shutdown()
    assert st.preemptions > 0, "no cache pressure — vacuous"
    for a, b in zip(refs, got):
        assert a.status == b.status == "finished"
        assert a.tokens == b.tokens


def test_spec_identity_with_prefix_cache_and_eviction(model):
    """Spec + prefix cache + eviction pressure compose: shared-prefix
    prompts served sequentially under a tight cache stay identical to
    the plain cold path, with real hits AND real evictions."""
    rng = np.random.RandomState(31)
    prefix = rng.randint(0, VOCAB, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(0, VOCAB, (5,)).astype(np.int32)])
               for _ in range(3)]
    churn = [rng.randint(0, VOCAB, (24,)).astype(np.int32)
             for _ in range(2)]
    order = [prompts[0], churn[0], prompts[1], churn[1], prompts[2]]

    ref_eng = _engine(model, prefix_cache=False)
    refs = []
    for p in order:
        refs.append(ref_eng.submit(p, max_new_tokens=8))
        ref_eng.run()
    ref_eng.shutdown()

    _, draft = _draft_of(model[1])
    eng = _engine(model, num_blocks=16, max_model_len=48,
                  **_spec_kw(draft))
    got = []
    for p in order:
        got.append(eng.submit(p, max_new_tokens=8))
        eng.run()
    st = eng.stats()
    eng.shutdown()
    assert st.prefix_hits > 0, "no prefix reuse — vacuous"
    assert st.prefix_evictions > 0, "no eviction pressure — vacuous"
    for a, b in zip(refs, got):
        assert a.tokens == b.tokens


def test_spec_identity_at_model_len_boundary(model):
    """The max_model_len boundary regression: a request whose final
    length fills its block table exactly must not over-reserve past
    the table (host crash) or clamp-write past it (cache clobber) —
    speculative positions beyond target_len route to the null block
    and the emit cap drops them."""
    _, draft = _draft_of(model[1])
    rng = np.random.RandomState(41)
    # prompt 20 + 12 generated == max_model_len 32 == the whole table
    prompts = [rng.randint(0, VOCAB, (20,)).astype(np.int32)
               for _ in range(3)]
    ref_eng = _engine(model, max_model_len=32)
    refs = _serve(ref_eng, prompts, max_new=12)
    ref_eng.shutdown()
    eng = _engine(model, max_model_len=32, **_spec_kw(draft, k=4))
    got = _serve(eng, prompts, max_new=12)
    eng.shutdown()
    for a, b in zip(refs, got):
        assert a.status == b.status == "finished"
        assert a.tokens == b.tokens
        assert len(b.tokens) == 12               # quota exactly honored


# -- k=0 inert path ----------------------------------------------------------
def test_spec_k0_is_byte_for_byte_inert(model):
    """spec_k=0 must be the PRE-SPEC engine: no draft worker, no
    verify buckets, the same warmup grid and the same AOT fingerprint
    — an upgraded spec-off fleet keeps loading its existing artifacts
    and manifests."""
    plain = _engine(model)
    off = _engine(model, spec_k=0)
    assert off._spec is None
    assert off.verify_buckets() == []
    assert off._warmup_grid() == plain._warmup_grid()
    assert off._aot_base_fp() == plain._aot_base_fp()
    assert off._spec_key() == plain._spec_key()
    assert off.statusz()["spec"] is None
    st = off.stats()
    assert st.spec_verifies == 0 and st.spec_accept_rate is None
    plain.shutdown()
    off.shutdown()


def test_spec_argument_validation(model):
    _, draft = _draft_of(model[1])
    # temperature > 0 with spec is now SERVED (rejection-sampling
    # acceptance, tests/test_sampling.py) — but an explicitly
    # greedy-only engine still refuses stochastic defaults
    with pytest.raises(ValueError, match="sampling"):
        _engine(model, temperature=0.7, sampling=False,
                **_spec_kw(draft))
    with pytest.raises(ValueError, match="draft_params"):
        _engine(model, spec_k=3)
    with pytest.raises(ValueError, match="spec_k"):
        _engine(model, spec_k=-1)
    # vocab mismatch: drafted ids feed the target verify directly
    S = 96
    net2 = mx.models.gpt(31, S, num_layers=1, d_model=32, num_heads=4)
    bad = _rand_params(net2, S, seed=5)
    with pytest.raises(ValueError, match="vocab"):
        _engine(model, spec_k=3, draft_params=bad, draft_num_heads=4,
                draft_window=0)


def test_spec_env_default(model, monkeypatch):
    """MXTPU_SERVE_SPEC is the env default; Engine(spec_k=) wins."""
    monkeypatch.setenv("MXTPU_SERVE_SPEC", "2")
    _, draft = _draft_of(model[1])
    eng = _engine(model, draft_params=draft, draft_num_heads=4,
                  draft_window=0)
    assert eng.spec_k == 2
    eng.shutdown()
    eng = _engine(model, spec_k=0)               # explicit arg wins
    assert eng.spec_k == 0 and eng._spec is None
    eng.shutdown()


# -- stats / statusz / metrics agreement -------------------------------------
def test_spec_stats_three_view_agreement(model):
    """ServeStats.spec_*, the statusz spec section and the telemetry
    registry series agree by construction (one feed), and the derived
    means are exactly the quotients of the raw counters."""
    telemetry.reset()
    telemetry.enable()
    try:
        _, draft = _draft_of(model[1])
        eng = _engine(model, **_spec_kw(draft))
        _serve(eng, _prompts())
        st = eng.stats()
        sz = eng.statusz()["spec"]
        snap = telemetry.registry().snapshot()
        eng.shutdown()

        def val(name):
            return snap[name]["samples"][0]["value"]

        assert st.spec_verifies > 0
        assert val("mxtpu_serve_spec_drafted_tokens_total") == \
            float(st.spec_drafted_tokens)
        assert val("mxtpu_serve_spec_accepted_tokens_total") == \
            float(st.spec_accepted_tokens)
        assert val("mxtpu_serve_spec_rejected_tokens_total") == \
            float(st.spec_rejected_tokens)
        assert st.accepted_per_verify == round(
            st.spec_accepted_tokens / st.spec_verifies, 4)
        assert st.spec_accept_rate == round(
            st.spec_accepted_tokens / st.spec_drafted_tokens, 4)
        assert st.decode_occupancy is not None
        # statusz: same k, same windowed view of the same stream
        assert sz["k"] == 3
        assert sz["draft"]["params_bytes"] > 0
        assert sz["window_verifies"] == st.spec_verifies
        assert sz["accept_rate_window"] == st.spec_accept_rate
        assert sz["verify_buckets"] == [1, 2, 4]
    finally:
        telemetry.disable()
        telemetry.reset()


def test_tok_s_accounting_counts_actual_emitted_tokens(model):
    """The satellite fix: tokens_generated (and so tok/s) must count
    ACTUAL emitted tokens, not iterations — with spec on, steps are
    far fewer than tokens."""
    target, draft = _draft_of(model[1], damp=0.05)
    eng = _engine(model, params=target, **_spec_kw(draft, k=4))
    reqs = _serve(eng, _prompts(), max_new=16)
    st = eng.stats()
    eng.shutdown()
    assert st.tokens_generated == sum(len(r.tokens) for r in reqs)
    # multi-token iterations: strictly fewer decode steps than tokens
    assert st.spec_accepted_tokens > 0
    assert st.steps < st.tokens_generated


def test_quota_capped_verify_does_not_inflate_acceptance(model):
    """Acceptance accounting counts only drafts actually EMITTED: a
    request with 1 token of quota left whose k=4 drafts all agree must
    record at most 1 accepted token, not 4 — otherwise short-generation
    workloads inflate spec_accept_rate (and the MIN_ACCEPT anomaly
    trigger judges a phantom rate)."""
    target, draft = _draft_of(model[1], damp=0.05)
    eng = _engine(model, params=target, **_spec_kw(draft, k=4))
    reqs = _serve(eng, _prompts(), max_new=2)
    st = eng.stats()
    eng.shutdown()
    # prefill emits token 1; the single verify iteration per request
    # is quota-capped to 1 emitted token
    assert st.spec_verifies == len(reqs)
    assert all(len(r.tokens) == 2 for r in reqs)
    assert st.spec_accepted_tokens <= st.spec_verifies


def test_draft_ledger_pruned_for_departed_requests(model):
    """The ingest ledger stays bounded by the LIVE running set: a rid
    that left the engine without passing the per-batch forget path
    (preempted, then rejected/cancelled) is pruned at the next step."""
    _, draft = _draft_of(model[1])
    eng = _engine(model, **_spec_kw(draft))
    _serve(eng, _prompts())                        # finished: forget path
    assert eng._spec.statusz(eng)["tracked_requests"] == 0
    ghost = type("R", (), {"rid": "ghost", "n_preemptions": 0})()
    eng._spec.note_ingested(ghost, 4)              # simulated leak
    assert eng._spec.statusz(eng)["tracked_requests"] == 1
    eng.submit(_prompts(ns=(5,))[0], max_new_tokens=2)
    eng.run()
    assert eng._spec.statusz(eng)["tracked_requests"] == 0
    eng.shutdown()


def test_monitor_line_carries_spec_tail(model, caplog):
    """ServeMonitor's line gains a ``spec=<rate>/<per-verify>`` tail
    once a verify has run — and stays byte-identical to the pre-spec
    format on a plain engine."""
    import logging

    logger = logging.getLogger("test_spec_monitor")
    _, draft = _draft_of(model[1])
    eng = _engine(model, **_spec_kw(draft))
    _serve(eng, _prompts(ns=(5,)))
    with caplog.at_level(logging.INFO, logger=logger.name):
        mx.monitor.ServeMonitor(eng, interval=1, logger=logger).log_now()
    eng.shutdown()
    assert " spec=" in caplog.messages[-1]

    plain = _engine(model)
    _serve(plain, _prompts(ns=(5,)))
    with caplog.at_level(logging.INFO, logger=logger.name):
        mx.monitor.ServeMonitor(plain, interval=1,
                                logger=logger).log_now()
    plain.shutdown()
    assert " spec=" not in caplog.messages[-1]
    assert "tok/s=" in caplog.messages[-1]


def test_low_acceptance_flight_dump(model, tmp_path, monkeypatch):
    """A rolling acceptance rate below MXTPU_SPEC_MIN_ACCEPT dumps a
    spec_low_acceptance flight anomaly (after MIN_WINDOW verifies) —
    the operator signal for a silently diverging draft."""
    monkeypatch.setenv("MXTPU_SPEC_MIN_ACCEPT", "0.9")
    monkeypatch.setenv(flight.ENV_DIR, str(tmp_path))
    flight.recorder().clear()
    try:
        _, draft = _draft_of(model[1])
        eng = _engine(model, **_spec_kw(draft))
        sw = eng._spec
        assert sw.min_accept == 0.9
        # below MIN_WINDOW: no judgement yet
        for _ in range(spec_mod.MIN_WINDOW - 1):
            sw.on_verify(3, 0)
        assert not list(tmp_path.glob("*.json"))
        sw.on_verify(3, 0)                       # window filled, rate 0.0
        dumps = list(tmp_path.glob("*.json"))
        assert len(dumps) == 1
        payload = json.loads(dumps[0].read_text())
        assert payload["reason"] == "spec_low_acceptance"
        assert payload["extra"]["accept_rate"] == 0.0
        assert payload["extra"]["threshold"] == 0.9
        eng.shutdown()
    finally:
        flight.recorder().clear()


# -- request traces / trace_report -------------------------------------------
def test_trace_events_carry_emitted_and_run_length(model, tmp_path,
                                                   monkeypatch):
    """Decode trace events stamp the per-iteration emitted count (>1
    under spec) and trace_report derives the mean accepted run length
    — with --check still reporting complete timelines."""
    trace_file = tmp_path / "rt.jsonl"
    monkeypatch.setenv("MXTPU_REQUEST_TRACE", str(trace_file))
    target, draft = _draft_of(model[1], damp=0.05)
    eng = _engine(model, params=target, **_spec_kw(draft, k=4))
    reqs = _serve(eng, _prompts(), max_new=16)
    eng.shutdown()

    lines = [json.loads(l) for l in open(trace_file)]
    assert len(lines) == len(reqs)
    saw_multi = False
    for line in lines:
        decode = [e for e in line["events"] if e["ev"] == "decode"]
        assert decode
        for e in decode:
            assert 1 <= e["emitted"] <= 5
            assert "accepted" in e
            saw_multi = saw_multi or e["emitted"] > 1
        # emitted sums to the request's generated total exactly (the
        # first token comes from the prefill pass, not a decode event)
        assert sum(e["emitted"] for e in decode) == line["generated"] - 1
    assert saw_multi, "no multi-token iteration — test is vacuous"

    import trace_report

    out = tmp_path / "report.json"
    assert trace_report.main([str(trace_file), "--json", str(out),
                              "--check"]) == 0
    summary = json.loads(open(out).read())
    assert summary["complete"] == len(reqs)
    assert summary["mean_run_len"] > 1.0
    assert summary["mean_run_len_per_request"] > 1.0
    assert summary["decode_tokens_emitted"] == \
        sum(len(r.tokens) - 1 for r in reqs)
    # pre-`emitted` trace files (older engines) still aggregate: one
    # token per decode event, run length exactly 1.0
    rec = dict(lines[0])
    rec["events"] = [dict(e) for e in rec["events"]]
    for e in rec["events"]:
        e.pop("emitted", None)
    iters, emitted = trace_report.decode_profile(rec["events"])
    assert iters == emitted > 0


# -- AOT: warmup grid + zero-fresh-trace warm restart ------------------------
@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _total(name, **labels):
    snap = telemetry.registry().snapshot()
    if name not in snap:
        return 0
    total = 0
    for s in snap[name]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
    return total


def test_verify_buckets_join_the_warmup_grid(model):
    """Spec adds exactly three program families to the grid: verify +
    draft (decode-batch buckets) and draft_chunk (prompt buckets) —
    and the pinned spec-off count is unchanged."""
    _, draft = _draft_of(model[1])
    plain = _engine(model, max_batch=2, max_model_len=16)
    grid_off = plain._warmup_grid()
    assert len(grid_off) == 12                     # the test_aot pin
    plain.shutdown()
    eng = _engine(model, max_batch=2, max_model_len=16,
                  **_spec_kw(draft))
    grid = eng._warmup_grid()
    kinds = {}
    for e in grid:
        kinds.setdefault(e["kind"], []).append(e["bucket"])
    assert kinds["verify"] == [1, 2]
    assert kinds["draft"] == [1, 2]
    assert kinds["draft_chunk"] == [1, 2, 4, 8, 16]
    assert len(grid) == 12 + 2 + 2 + 5             # 21: off-grid + spec
    assert eng.warmup() == 21
    eng.shutdown()


def test_spec_warm_restart_zero_fresh_traces(tel, tmp_path, model):
    """The acceptance gate: a spec-enabled engine's manifest replayed
    into a fresh process-simulated restart loads EVERY program — the
    verify/draft/draft_chunk families included — from the export
    store, traces nothing, and serves token-identical output."""
    engine_mod._STEP_CACHE.clear()
    aot_dir = str(tmp_path / "aot")
    _, draft = _draft_of(model[1])
    prompts = _prompts(ns=(7, 12, 5))
    kw = dict(max_batch=2, max_model_len=32, aot_dir=aot_dir,
              **_spec_kw(draft))

    cold = _engine(model, **kw)
    toks_cold = [r.tokens for r in _serve(cold, prompts)]
    manifest = cold.manifest()
    cold.shutdown()
    assert {e["kind"] for e in manifest} >= {"verify", "draft"}

    engine_mod._STEP_CACHE.clear()                 # simulated restart
    traces = _total("mxtpu_aot_programs_total", source="trace")

    warm = _engine(model, **kw)
    warmed = warm.warmup(manifest)
    assert warmed == len(manifest)
    assert _total("mxtpu_aot_programs_total", source="trace") == traces
    assert _total("mxtpu_aot_programs_total", source="artifact") == warmed
    toks_warm = [r.tokens for r in _serve(warm, prompts)]
    assert toks_warm == toks_cold
    assert _total("mxtpu_aot_programs_total", source="trace") == traces
    warm.shutdown()
    engine_mod._STEP_CACHE.clear()


def test_spec_fingerprint_keys_k_and_draft(model):
    """Artifacts must key on (spec_k, draft config): engines differing
    only there can never serve each other's programs."""
    _, draft = _draft_of(model[1])
    a = _engine(model, **_spec_kw(draft, k=2))
    b = _engine(model, **_spec_kw(draft, k=3))
    assert a._aot_base_fp() != b._aot_base_fp()
    assert a._spec_key() != b._spec_key()
    a.shutdown()
    b.shutdown()


# -- bench contract (slow) ---------------------------------------------------
@pytest.mark.slow
def test_spec_bench_contract(tmp_path):
    """tools/serve_bench.py --workload spec (SPEC_BENCH.json) emits the speculative A/B record on CPU smoke
    shapes: byte-identical tokens, a measured (non-vacuous) acceptance
    rate, and the complete:true contract the serve_spec stage gates."""
    import subprocess

    out = tmp_path / "spec.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "spec",
         "--layers", "2", "--d-model", "64", "--heads", "4",
         "--kv-heads", "2", "--vocab", "211", "--requests", "12",
         "--concurrency", "4", "--prompt-lens", "16,24,32",
         "--max-new", "24", "--json", str(out)],
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["complete"] is True
    assert payload["tokens_identical"] is True
    assert payload["spec_k"] == 4
    assert 0 < payload["spec_accept_rate"] <= 1.0
    assert payload["accepted_per_verify"] > 0
    assert payload["tokens_per_sec_on"] > 0
    assert payload["tokens_per_sec_off"] > 0
