"""Per-request sampling as traced operands (mxnet_tpu/serve/programs.py)
and rejection-sampled speculative decoding (mxnet_tpu/serve/spec.py).

The contracts under test:

* trace-key inertness — a greedy-only engine (sampling off, the
  default) keeps the HISTORICAL programs: same `_spec_key`, same AOT
  fingerprint fields (temperature/top_k re-emitted, no sampling keys),
  same warmup grid, same tokens;
* operands, not trace keys — ONE warmed bucketed program serves any
  mix of per-request temperature/top-p/top-k (greedy rows included)
  with ZERO fresh traces, and flipping a request's temperature never
  recompiles;
* statistics — the operand sampler's empirical distributions match the
  analytic warped softmax (temperature/top-k/top-p, TV-distance pins
  on a tiny vocab), the `jax.lax.top_k` formulation is numerically
  equivalent to the old full-vocab-sort one, and rejection-sampled
  speculative decoding at temperature>0 produces the same output
  distribution as plain sampling (two-sample chi-square across seeds);
* n>1 — siblings share the prompt's radix-cached prefix blocks
  copy-on-write: one prefill pays for all n (pinned via prefix_stats
  and physical block-table overlap);
* logprobs — every emitted token's raw logprob plus the top-k view,
  from the same dispatch;
* the fleet replica accepts per-request sampling params with clean
  400s for malformed values (never 500s that would open breakers).
"""

import collections
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.serve import programs as programs_mod

VOCAB = 53


@pytest.fixture(scope="module")
def model():
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4)
    return net, _rand_params(net, S, seed=3)


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


def _draft_of(params, damp=0.05):
    src = dict(params)
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


def _prompts(ns=(7, 12, 5, 9), seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (n,)).astype(np.int32) for n in ns]


def _cfg(sampling=True, cap=64):
    return programs_mod._ModelCfg(
        name="gpt", n_layers=2, num_heads=4, head_dim=8, kv_heads=4,
        pos_table=96, swiglu=False, tied=False, rmsnorm=False, window=0,
        block_size=4, sampling=sampling, sample_cap=cap,
        numeric_watch=False, kv_quant=False)


def _tv(counts_a, counts_b):
    na, nb = sum(counts_a.values()), sum(counts_b.values())
    return 0.5 * sum(abs(counts_a.get(c, 0) / na - counts_b.get(c, 0) / nb)
                     for c in set(counts_a) | set(counts_b))


# -- submit-time validation ---------------------------------------------------
def test_submit_param_validation(model):
    eng = _engine(model, sampling=True)
    p = _prompts()[0]
    with pytest.raises(ValueError, match="temperature"):
        eng.submit(p, temperature=-0.5)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(p, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(p, top_p=1.5)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(p, top_k=-3)
    with pytest.raises(ValueError, match="logprobs"):
        eng.submit(p, logprobs=99)
    with pytest.raises(ValueError, match="n must"):
        eng.submit(p, n=0)
    eng.shutdown()
    # a greedy-only engine refuses per-request sampling cleanly
    eng = _engine(model)
    assert not eng._sampling
    with pytest.raises(ValueError, match="sampling"):
        eng.submit(p, temperature=0.7)
    with pytest.raises(ValueError, match="sampling"):
        eng.submit(p, logprobs=2)
    eng.shutdown()
    # stochastic defaults cannot combine with an explicit sampling=False
    with pytest.raises(ValueError, match="sampling"):
        _engine(model, temperature=0.5, sampling=False)


def test_sampling_env_default(model, monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_SAMPLING", "1")
    monkeypatch.setenv("MXTPU_SERVE_SAMPLE_CAP", "32")
    eng = _engine(model)
    assert eng._sampling and eng.sample_cap == 32
    assert eng.statusz()["sampling"]["sample_cap"] == 32
    eng.shutdown()
    monkeypatch.delenv("MXTPU_SERVE_SAMPLING")
    eng = _engine(model)                        # default: greedy-only
    assert not eng._sampling
    assert eng.statusz()["sampling"] is None
    eng.shutdown()


# -- greedy (sampling-off) inertness ------------------------------------------
def test_greedy_engine_keeps_historical_fingerprint(model):
    """The only-when-on rule: a greedy engine's fingerprint re-emits
    the historical temperature/top_k trace-key fields and never grows
    sampling keys — an upgraded greedy fleet keeps its artifacts."""
    a = _engine(model)
    b = _engine(model)
    fp = a._aot_base_fp()
    assert fp["cfg"]["temperature"] == 0.0
    assert fp["cfg"]["top_k"] is None
    assert "sampling" not in fp["cfg"] and "sample_cap" not in fp["cfg"]
    assert a._spec_key() == b._spec_key()
    assert a._aot_base_fp() == b._aot_base_fp()
    assert a._warmup_grid() == b._warmup_grid()
    # the sampling engine is a DIFFERENT program family
    c = _engine(model, sampling=True)
    assert c._spec_key() != a._spec_key()
    fpc = c._aot_base_fp()
    assert fpc["cfg"]["sampling"] is True
    assert "temperature" not in fpc["cfg"]
    # same kinds and buckets though: sampling changes no grid shape
    assert c._warmup_grid() == a._warmup_grid()
    for e in (a, b, c):
        e.shutdown()


# -- zero fresh traces for heterogeneous configs ------------------------------
def test_mixed_configs_zero_fresh_traces(model):
    """THE tentpole pin: after warmup, a batch mixing greedy rows with
    distinct temperature/top-p/top-k asks (and then flipping every
    request's temperature) compiles NOTHING new — the params are
    operands, not trace keys."""
    eng = _engine(model, sampling=True)
    eng.warmup()
    before = len(engine_mod._STEP_CACHE)
    cfgs = [{}, {"temperature": 0.8}, {"temperature": 1.1, "top_k": 7},
            {"temperature": 0.6, "top_p": 0.7, "logprobs": 2}]
    reqs = [eng.submit(p, max_new_tokens=6, **c)
            for p, c in zip(_prompts(), cfgs)]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    assert len(engine_mod._STEP_CACHE) == before, \
        "mixed sampling configs traced fresh programs"
    # temp-flip-without-recompile: same prompts, different params
    flip = [{"temperature": 1.3}, {}, {"temperature": 0.2, "top_k": 3},
            {"top_p": 0.5, "temperature": 0.9}]
    reqs = [eng.submit(p, max_new_tokens=6, **c)
            for p, c in zip(_prompts(), flip)]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    assert len(engine_mod._STEP_CACHE) == before, \
        "flipping per-request temperature recompiled"
    eng.shutdown()


def test_greedy_rows_byte_identical_across_modes(model):
    """A temp-0 row in a sampling-mode batch (co-scheduled with
    stochastic peers) emits exactly the greedy-only engine's tokens."""
    prompts = _prompts(ns=(9, 11, 6, 8), seed=23)
    ref = _engine(model)
    refs = [ref.submit(p, max_new_tokens=10) for p in prompts]
    ref.run()
    ref.shutdown()
    eng = _engine(model, sampling=True)
    got = [eng.submit(prompts[0], max_new_tokens=10),
           eng.submit(prompts[1], max_new_tokens=10, temperature=1.0),
           eng.submit(prompts[2], max_new_tokens=10),
           eng.submit(prompts[3], max_new_tokens=10, top_k=4,
                      temperature=0.8)]
    eng.run()
    eng.shutdown()
    assert got[0].tokens == refs[0].tokens
    assert got[2].tokens == refs[2].tokens


# -- sampler statistics -------------------------------------------------------
def test_lax_topk_matches_sort_reference():
    """Satellite pin: the `jax.lax.top_k` warp is numerically
    equivalent to the old full-vocab `jnp.sort` formulation — same
    kept-candidate sets, same warped probabilities."""
    cfg = _cfg(cap=64)
    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(16, VOCAB).astype(np.float32))
    temp = jnp.full((16,), 0.7, jnp.float32)
    topp = jnp.ones((16,), jnp.float32)
    for kk in (1, 3, 10, VOCAB):
        topk = jnp.full((16,), kk, jnp.int32)
        got = np.asarray(programs_mod._filtered_probs_full(
            cfg, logits, temp, topp, topk))
        # the historical formulation: full sort, kth-largest threshold
        lg = np.asarray(logits, np.float32) / 0.7
        kth = np.sort(lg, axis=-1)[:, -kk][:, None]
        masked = np.where(lg >= kth, lg, -np.inf)
        ref = np.exp(masked - masked.max(-1, keepdims=True))
        ref = ref / ref.sum(-1, keepdims=True)
        assert np.allclose(got, ref, atol=1e-6), f"top_k={kk}"


def test_sampler_distribution_pins():
    """TV-distance pins of the operand sampler against the analytic
    warped distribution on a tiny vocab (cap >= vocab, so the cap is
    not a factor): temperature-only, top-k, top-p, and greedy."""
    V, M = 13, 4000
    cfg = _cfg(cap=64)
    rng = np.random.RandomState(11)
    row = rng.randn(V).astype(np.float32)
    logits = jnp.asarray(np.tile(row, (M, 1)))

    def draws(temp, top_p, top_k, seed=0):
        toks = programs_mod._sample_ops(
            cfg, logits, jax.random.PRNGKey(seed),
            jnp.full((M,), temp, jnp.float32),
            jnp.full((M,), top_p, jnp.float32),
            jnp.full((M,), top_k, jnp.int32))
        return collections.Counter(np.asarray(toks).tolist())

    def analytic(temp, top_p, top_k):
        lg = row / temp
        order = np.argsort(-lg)
        keep = np.zeros(V, bool)
        kk = top_k if top_k else V
        keep[order[:kk]] = True
        p = np.where(keep, np.exp(lg - lg.max()), 0.0)
        p = p / p.sum()
        csum = np.cumsum(p[order])
        drop = (csum - p[order]) >= top_p
        keep[order[drop]] = False
        p = np.where(keep, p, 0.0)
        return {i: v / p.sum() for i, v in enumerate(p) if v > 0}

    for temp, top_p, top_k in ((0.8, 1.0, 0), (1.3, 1.0, 4),
                               (0.6, 0.75, 0), (1.0, 0.9, 6)):
        got = draws(temp, top_p, top_k)
        want = analytic(temp, top_p, top_k)
        tv = 0.5 * sum(abs(got.get(c, 0) / M - want.get(c, 0.0))
                       for c in set(got) | set(want))
        assert tv < 0.05, (temp, top_p, top_k, tv)
        assert set(got) <= set(want), "sampled outside the filtered set"
    # greedy rows are exact argmax, deterministically
    toks = programs_mod._sample_ops(
        cfg, logits[:8], jax.random.PRNGKey(3),
        jnp.zeros((8,), jnp.float32), jnp.ones((8,), jnp.float32),
        jnp.zeros((8,), jnp.int32))
    assert np.asarray(toks).tolist() == [int(np.argmax(row))] * 8


def _pair_counts(model, params, ekw, prompt, m, temp, seeds=(0, 1)):
    out = collections.Counter()
    per = m // len(seeds)
    for seed in seeds:
        eng = _engine(model, params=params, seed=seed, num_blocks=128,
                      max_batch=8, max_queue=per + 1, **ekw)
        reqs = [eng.submit(prompt, max_new_tokens=2, temperature=temp)
                for _ in range(per)]
        eng.run()
        eng.shutdown()
        out.update((r.tokens[0], r.tokens[1]) for r in reqs
                   if len(r.tokens) == 2)
    return out


def test_spec_sampling_distribution_identity(model):
    """Acceptance gate: rejection-sampled speculative decoding at
    temperature>0 emits the SAME distribution as plain sampling —
    two-sample chi-square over (token0, token1) pairs across seeds on
    a tiny vocab, spec-on vs spec-off."""
    target, draft = _draft_of(model[1])
    prompt = _prompts(ns=(9,), seed=41)[0]
    spec_kw = dict(spec_k=3, draft_params=draft, draft_num_heads=4,
                   draft_window=0, sampling=True)
    a = _pair_counts(model, target, dict(sampling=True), prompt,
                     360, 0.8, seeds=(0, 1, 2))
    b = _pair_counts(model, target, spec_kw, prompt,
                     360, 0.8, seeds=(3, 4, 5))
    na, nb = sum(a.values()), sum(b.values())
    assert na > 300 and nb > 300
    cats = [c for c in set(a) | set(b)
            if a.get(c, 0) + b.get(c, 0) >= 10]
    rows = [(a.get(c, 0), b.get(c, 0)) for c in cats]
    rows.append((sum(v for c, v in a.items() if c not in cats),
                 sum(v for c, v in b.items() if c not in cats)))
    stat = 0.0
    for xa, xb in rows:
        tot = xa + xb
        ea, eb = tot * na / (na + nb), tot * nb / (na + nb)
        stat += ((xa - ea) ** 2 / ea if ea else 0.0)
        stat += ((xb - eb) ** 2 / eb if eb else 0.0)
    df = max(1, len(rows) - 1)
    z = (stat - df) / (2 * df) ** 0.5
    assert abs(z) < 5, (z, rows)


def test_spec_sampling_runs_and_splits_stats(model):
    """Spec at temperature>0 serves (the restriction is lifted), and
    the greedy-vs-stochastic acceptance split agrees across ServeStats
    / statusz / the telemetry registry (three views, one feed)."""
    telemetry.reset()
    telemetry.enable()
    try:
        target, draft = _draft_of(model[1])
        eng = _engine(model, params=target, sampling=True, spec_k=3,
                      draft_params=draft, draft_num_heads=4,
                      draft_window=0)
        # mixed batch: greedy rows AND stochastic rows through the
        # same rejection-sampling verify program
        reqs = [eng.submit(p, max_new_tokens=10, temperature=t)
                for p, t in zip(_prompts(), (0.0, 0.7, 0.0, 0.9))]
        eng.run()
        st = eng.stats()
        sz = eng.statusz()["spec"]
        snap = telemetry.registry().snapshot()
        eng.shutdown()
        assert all(r.status == "finished" for r in reqs)
        assert st.spec_verifies > 0
        assert st.spec_drafted_tokens_stochastic > 0
        assert st.spec_drafted_tokens > st.spec_drafted_tokens_stochastic
        assert st.spec_accept_rate_stochastic == \
            sz["accept_rate_stochastic"]
        assert st.spec_accept_rate_greedy == sz["accept_rate_greedy"]

        def val(name, mode):
            samples = snap[name]["samples"]
            return sum(s["value"] for s in samples
                       if s["labels"].get("mode") == mode)

        drafted_s = val("mxtpu_serve_spec_mode_drafted_tokens_total",
                        "stochastic")
        accepted_s = val("mxtpu_serve_spec_mode_accepted_tokens_total",
                         "stochastic")
        assert drafted_s == st.spec_drafted_tokens_stochastic
        assert accepted_s == st.spec_accepted_tokens_stochastic
        drafted_g = val("mxtpu_serve_spec_mode_drafted_tokens_total",
                        "greedy")
        assert drafted_g == (st.spec_drafted_tokens
                             - st.spec_drafted_tokens_stochastic)
    finally:
        telemetry.disable()
        telemetry.reset()


def test_spec_sampling_greedy_rows_identical(model):
    """The degenerate-exactness pin: on a sampling engine WITH spec,
    a temp-0 request's rejection-sampled acceptance (one-hot p and q)
    emits byte-for-byte what the plain greedy engine emits."""
    target, draft = _draft_of(model[1])
    prompts = _prompts(ns=(8, 13, 6), seed=33)
    ref = _engine(model, params=target)
    refs = [ref.submit(p, max_new_tokens=12) for p in prompts]
    ref.run()
    ref.shutdown()
    eng = _engine(model, params=target, sampling=True, spec_k=3,
                  draft_params=draft, draft_num_heads=4, draft_window=0)
    got = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run()
    st = eng.stats()
    eng.shutdown()
    assert st.spec_verifies > 0
    for a, b in zip(refs, got):
        assert a.status == b.status == "finished"
        assert a.tokens == b.tokens


# -- n>1 COW samples ----------------------------------------------------------
def test_n_samples_share_prefix_cow(model):
    """n>1 pin: the siblings' radix walk shares the primary's
    published prompt blocks copy-on-write — one prefill pays for all
    n (prefill compute ~= prompt + (n-1) * final-span recompute), the
    tables physically overlap, and shared blocks are refcounted."""
    eng = _engine(model, sampling=True, max_batch=4)
    rng = np.random.RandomState(51)
    prompt = rng.randint(0, VOCAB, (17,)).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=6, temperature=0.9, n=3)
    assert req.samples is not None and len(req.samples) == 3
    assert [s.sample_index for s in req.samples] == [0, 1, 2]
    assert all(s.group == req.rid for s in req.samples)
    eng.step()                      # primary prefill publishes blocks
    eng.step()                      # siblings released + admitted
    tables = {s.rid: list(eng.blocks.table(s.rid))
              for s in req.samples if eng.blocks.table(s.rid)}
    prim = set(tables.get(req.rid, []))
    shared = [set(t) & prim for rid, t in tables.items()
              if rid != req.rid]
    assert shared and all(len(s) >= 17 // 4 - 1 for s in shared), \
        "siblings did not share the primary's prompt blocks"
    eng.run()
    st = eng.stats()
    eng.shutdown()
    assert all(s.status == "finished" for s in req.samples)
    assert st.prefix_hits == 2              # each sibling hit once
    assert st.prefix_tokens_saved == 2 * 16  # 4 full blocks each
    # one real prefill + two 1-token COW recomputes of the final span
    assert st.prefill_tokens_computed == 17 + 2 * 1


def test_n_samples_greedy_are_identical_and_validated(model):
    # greedy n>1 duplicates are allowed (and equal); the prefix cache
    # is required for the COW contract
    eng = _engine(model, max_batch=4)
    prompt = _prompts(ns=(9,), seed=61)[0]
    req = eng.submit(prompt, max_new_tokens=5, n=2)
    eng.run()
    assert [s.status for s in req.samples] == ["finished"] * 2
    assert req.samples[0].tokens == req.samples[1].tokens
    eng.shutdown()
    eng = _engine(model, prefix_cache=False)
    with pytest.raises(ValueError, match="prefix cache"):
        eng.submit(prompt, n=2)
    eng.shutdown()


# -- logprobs -----------------------------------------------------------------
def test_logprob_outputs(model):
    eng = _engine(model, sampling=True)
    p = _prompts(ns=(10,), seed=71)[0]
    greedy = eng.submit(p, max_new_tokens=6, logprobs=3)
    stoch = eng.submit(p, max_new_tokens=6, temperature=0.9, logprobs=5)
    plain = eng.submit(p, max_new_tokens=6)
    eng.run()
    eng.shutdown()
    for r, want in ((greedy, 3), (stoch, 5)):
        assert len(r.token_logprobs) == len(r.tokens)
        assert len(r.top_logprobs) == len(r.tokens)
        for row, lp in zip(r.top_logprobs, r.token_logprobs):
            assert len(row) == want
            vals = [v for _, v in row]
            assert vals == sorted(vals, reverse=True)
            assert all(v <= 0.0 for v in vals)
            # the chosen token's logprob can never beat the top-1
            assert lp <= vals[0] + 1e-6
    # a greedy request's chosen token IS the top-1 candidate
    for tok, lp, row in zip(greedy.tokens, greedy.token_logprobs,
                            greedy.top_logprobs):
        assert row[0][0] == tok
        assert abs(row[0][1] - lp) < 1e-6
    # logprobs=0: the chosen-token logprobs still record (sampling
    # mode), the top view stays empty
    assert len(plain.token_logprobs) == len(plain.tokens)
    assert plain.top_logprobs == []


# -- request traces -----------------------------------------------------------
def test_admit_trace_carries_sampling_params(model, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    os.environ["MXTPU_REQUEST_TRACE"] = path
    try:
        eng = _engine(model, sampling=True)
        plain = eng.submit(_prompts()[0], max_new_tokens=3)
        stoch = eng.submit(_prompts()[1], max_new_tokens=3,
                           temperature=0.8, top_k=5, logprobs=2)
        eng.run()
        eng.shutdown()
    finally:
        del os.environ["MXTPU_REQUEST_TRACE"]
    lines = [json.loads(ln) for ln in open(path)]
    by_rid = {ln["rid"]: ln for ln in lines}

    def admit(rid):
        return next(e for e in by_rid[rid]["events"]
                    if e["ev"] in ("admitted", "resumed"))

    # plain greedy request: NO sampling field (line schema unchanged)
    assert "sampling" not in admit(plain.rid)
    samp = admit(stoch.rid)["sampling"]
    assert samp["temperature"] == 0.8
    assert samp["top_k"] == 5 and samp["logprobs"] == 2


# -- preemption composes ------------------------------------------------------
def test_stochastic_requests_survive_preemption(model):
    """Stochastic requests under cache pressure complete (identity is
    a greedy-only contract; distribution is seed-dependent either
    way — the pin is that resume-by-recomputation serves them)."""
    eng = _engine(model, sampling=True, num_blocks=18,
                  max_model_len=48)
    prompts = _prompts(ns=(12, 9, 14, 7, 11), seed=81)
    reqs = [eng.submit(p, max_new_tokens=12, temperature=0.8)
            for p in prompts]
    eng.run()
    st = eng.stats()
    eng.shutdown()
    assert st.preemptions > 0, "no cache pressure — vacuous"
    assert all(r.status == "finished" for r in reqs)
    assert all(len(r.tokens) == 12 for r in reqs)
    assert all(len(r.token_logprobs) == 12 for r in reqs)


# -- fleet replica ------------------------------------------------------------
def _post(url, path, payload, timeout=30):
    req = urllib.request.Request(
        f"{url}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_replica_sampling_params_and_clean_400s(model):
    from mxnet_tpu.fleet.replica import ReplicaServer

    rep = ReplicaServer(_engine(model, sampling=True),
                        replica_id="samp").start()
    try:
        code, out = _post(rep.url, "/generate",
                          {"prompt": [3, 5, 7], "max_new_tokens": 4,
                           "temperature": 0.9, "top_k": 6, "n": 2,
                           "logprobs": 2})
        assert code == 200
        assert len(out["tokens"]) == 4
        assert len(out["samples"]) == 2
        for s in out["samples"]:
            assert len(s["tokens"]) == 4
            assert len(s["token_logprobs"]) == 4
            assert all(len(row) == 2 for row in s["top_logprobs"])
        assert out["token_logprobs"] == out["samples"][0]["token_logprobs"]
        # regression: a primary that FINISHES in its very first step
        # (max_new=1) must not strand the engine-side siblings — the
        # replica pump polls engine.has_work(), which counts the
        # pending fanout even when the scheduler is empty
        code, out = _post(rep.url, "/generate",
                          {"prompt": [2, 4, 6, 8], "max_new_tokens": 1,
                           "temperature": 0.8, "n": 3}, timeout=30)
        assert code == 200
        assert len(out["samples"]) == 3
        assert all(len(s["tokens"]) == 1 for s in out["samples"])
        # malformed sampling params: clean 400s, never 500s (a 500
        # counts as a transport failure and opens breakers fleet-wide)
        for bad in ({"temperature": "spicy"}, {"temperature": -1},
                    {"top_p": 0}, {"top_p": 2.0}, {"top_k": -1},
                    {"n": 0}, {"n": 10_000}, {"logprobs": 99},
                    {"logprobs": "all"}):
            code, out = _post(rep.url, "/generate",
                              dict({"prompt": [3, 5], "max_new_tokens": 2},
                                   **bad))
            assert code == 400, (bad, code, out)
            assert out["retriable"] is False
    finally:
        rep.stop()
    # a greedy-only replica rejects sampling asks as a clean 400 too
    rep = ReplicaServer(_engine(model), replica_id="greedy").start()
    try:
        code, out = _post(rep.url, "/generate",
                          {"prompt": [3, 5], "max_new_tokens": 2,
                           "temperature": 0.7})
        assert code == 400 and out["retriable"] is False
        code, out = _post(rep.url, "/generate",
                          {"prompt": [3, 5], "max_new_tokens": 2})
        assert code == 200                     # plain traffic untouched
    finally:
        rep.stop()


def test_router_forwards_sampling_params(model):
    from mxnet_tpu.fleet.replica import ReplicaServer
    from mxnet_tpu.fleet.router import Router

    rep = ReplicaServer(_engine(model, sampling=True),
                        replica_id="r0").start()
    router = Router([rep.url])
    try:
        res = router.generate([3, 5, 7], max_new_tokens=3,
                              temperature=0.8, n=2, logprobs=1)
        assert len(res.tokens) == 3
        assert len(res.samples) == 2
        assert len(res.token_logprobs) == 3
        # plain request: no sampling keys on the wire, plain payload
        res = router.generate([3, 5, 7], max_new_tokens=3)
        assert res.samples is None and res.token_logprobs is None
    finally:
        router.stop()
        rep.stop()
