"""The serve step loop runs one pass ahead of what it has read
(mxnet_tpu/serve/engine.py, ``Engine._step_inner``).

What is held here, on the CPU at tiny sizes: for every kind of engine the
loop running ahead and the SAME loop held at depth 0 (every pass enqueued
with nothing unread) give the same tokens, logprobs, cache rows and state
rows; what a caller sees between two ``step()`` calls is one settled
snapshot; the token pool's small programs come ready with the manifest
entries they serve and are no manifest kind of their own.  Counts and
values only, never a time.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

import mxnet_tpu as mx
from mxnet_tpu.models import moe as M
from mxnet_tpu.serve import adapters as adapters_mod
from mxnet_tpu.serve import engine as engine_mod

VOCAB = 53
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


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


@pytest.fixture(scope="module")
def gpt():
    net = mx.models.gpt(VOCAB, 96, num_layers=2, d_model=32, num_heads=4)
    return net, _rand_params(net, 96, seed=3)


@pytest.fixture(scope="module")
def windowed():
    net = mx.models.gpt(VOCAB, 96, num_layers=2, d_model=32, num_heads=4,
                        attn_window=8)
    return net, _rand_params(net, 96, seed=4)


@pytest.fixture(scope="module")
def hybrid():
    dec = mx.models.hybrid_decoder(
        61, 32, ["mamba", "mamba", "attention", "mamba"] * 2, num_heads=4,
        kv_heads=2, d_ff=48, mamba_heads=4, mamba_head_dim=16,
        mamba_state=16, mamba_chunk=8)
    return dec, dec.init_params(3)


@pytest.fixture(scope="module")
def routed():
    dec = M.moe_decoder(
        61, 32, [M.FULL, M.WINDOW, M.WINDOW, M.WINDOW] * 2,
        heads=[4, 6, 6, 6] * 2, kv_heads=2, head_dim=16, window=8,
        ffn_types=["dense"] + ["moe"] * 7, d_ff=48, num_experts=16, top_k=3,
        expert_ff=24, shared_ff=24, routed_scale=2.5, experts_held=(4, 8))
    return dec, dec.init_params(3)


@pytest.fixture(scope="module")
def branch():
    dec = mx.models.branch_decoder(
        61, 32, ["mamba", "moe", "attention", "moe"] * 2, num_heads=4,
        kv_heads=2, head_dim=16, mamba_heads=4, mamba_head_dim=8,
        mamba_state=16, mamba_groups=2, mamba_chunk=8, num_experts=16,
        top_k=3, expert_ff=24, shared_ff=40, latent=16, routed_scale=2.5,
        experts_held=(4, 8))
    return dec, dec.init_params(3)


def _prompts(lens, vocab=VOCAB, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


def _lora(params, rank=4, seed=11, scale=0.1):
    rng = np.random.RandomState(seed)
    stems = adapters_mod.gpt_stems("gpt", 2, False, False, params)
    return {stem: ((rng.randn(rank, din) * scale).astype(np.float32),
                   (rng.randn(dout, rank) * scale).astype(np.float32))
            for stem, (dout, din) in stems.items()}


# kind -> (model fixture, engine arguments, prompt lengths, new tokens,
#          submit arguments per request or None, what the run must show)
GEO = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64,
           prefill_chunk=0)
KINDS = {
    "gpt_greedy": ("gpt", dict(GEO, max_prefills_per_step=2),
                   (9, 21, 13, 30, 5, 17), (6, 10, 3, 8, 12, 1), None, None),
    "gpt_sampled": ("gpt", dict(GEO, temperature=0.8, top_k=12, seed=5),
                    (9, 14, 6), (8, 8, 8),
                    [dict(logprobs=2), dict(top_p=0.9), dict(temperature=0.0)],
                    None),
    "windowed": ("windowed", dict(GEO), (19, 11, 25), (14, 9, 6), None, None),
    "chunked_beside_decode": ("gpt", dict(GEO, prefill_chunk=8),
                              (7, 29, 22), (16, 6, 6), None, "chunks"),
    "prefix_hits": ("gpt", dict(GEO, prefix_cache=True),
                    (19, 19, 19), (5, 7, 4), None, "cached"),
    "preempted": ("gpt", dict(GEO, num_blocks=20, max_prefills_per_step=2),
                  (12, 17, 22, 9), (24, 24, 24, 24), None, "preempt"),
    "lora": ("gpt", dict(GEO, adapters=3, adapter_rank=4,
                         max_prefills_per_step=2),
             (7, 12, 5, 9), (8, 8, 8, 8),
             [dict(), dict(adapter_id="a"), dict(adapter_id="b"),
              dict(adapter_id="a")], None),
    "hybrid": ("hybrid", dict(GEO, prefill_chunk=16), (9, 21, 13), (8, 6, 10),
               None, "state"),
    "routed_window": ("routed", dict(GEO, prefill_chunk=16), (9, 21, 13),
                      (12, 6, 10), None, "window"),
    "one_branch": ("branch", dict(GEO, prefill_chunk=16), (9, 21, 13),
                   (8, 6, 10), None, "state"),
    "speculative": ("gpt", dict(GEO, spec_k=2), (9, 14), (7, 5), None,
                    "spec"),
}


def _build(kind, request, hold):
    fixture, kw, lens, news, subs, _ = KINDS[kind]
    sym, params = request.getfixturevalue(fixture)
    kw = dict(kw)
    if kind == "speculative":
        kw.update(draft_params={k: v for k, v in params.items()
                                if not k.startswith("gpt_l1_")},
                  draft_num_heads=4, draft_window=0)
    eng = mx.serve.Engine(params, symbol=sym, **kw)
    if kind == "lora":
        for aid, seed in (("a", 21), ("b", 22)):
            eng.adapter_store.register(aid, _lora(params, seed=seed),
                                       alpha=8.0)
    if hold:
        # the same loop at depth 0: no pass is enqueued behind an unread one
        eng._hold = lambda: "held"
    same = kind == "prefix_hits"
    vocab = 61 if fixture in ("hybrid", "routed") else VOCAB
    prompts = _prompts(lens, vocab)
    if same:
        prompts = [prompts[0]] * len(prompts)
    reqs = [eng.submit(p, max_new_tokens=n, **((subs or [{}] * 9)[i]))
            for i, (p, n) in enumerate(zip(prompts, news))]
    return eng, reqs


def _rows(eng, req):
    """The K/V rows the cache holds for ``req`` (global group), by
    position, and its state-pool rows: what the served tokens stand on."""
    n = int(req.cache_len)
    table = np.asarray(eng.blocks.table(req.rid))
    out = []
    for cache in (eng._cache_k, eng._cache_v):
        c = np.asarray(cache)[:, table]          # (L, blocks, bs, ...)
        out.append(c.reshape(c.shape[0], -1, *c.shape[3:])[:, :n])
    if eng._state_ssm is not None:
        slot = eng.blocks.state_slot(req.rid)
        out += [np.asarray(eng._state_ssm)[:, slot],
                np.asarray(eng._state_conv)[:, slot]]
    return out


def _serve(kind, request, hold):
    """Step to the end, reading ``stats()`` between calls; returns what
    each request came to and, per (request, tokens so far), the snapshot a
    caller saw: ``cache_len`` and the request's cache and state rows."""
    eng, reqs = _build(kind, request, hold)
    seen, steps = {}, 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 500
        if eng._flight:
            # a pass is enqueued and unread: there is work, and nothing
            # of it shows yet
            assert eng.has_work()
            assert sum(r.flight_tokens for r in reqs) > 0 \
                or sum(r.flight_len for r in reqs) > 0
        eng.stats()                      # a reader: reads what is unread
        assert not eng._flight
        for i, r in enumerate(reqs):
            assert r.flight_len == 0 and r.flight_tokens == 0
            if r.done or r.status != "running" or not r.tokens:
                continue
            # tokens, positions and rows moved together
            assert r.cache_len == r.prompt.size + len(r.tokens) - 1
            seen.setdefault((i, len(r.tokens)), (r.cache_len, _rows(eng, r)))
    assert not eng._flight and not eng.has_work()
    out = {"tokens": [list(map(int, r.tokens)) for r in reqs],
           "logprobs": [list(r.token_logprobs) for r in reqs],
           "top": [list(r.top_logprobs) for r in reqs],
           "status": [r.status for r in reqs], "seen": seen,
           "order": eng.statusz()["step_order"], "stats": eng.stats(),
           "cached": [r.cached_prefix_len for r in reqs],
           "passes": [r.prefill_passes for r in reqs],
           "window": (None if eng.blocks.window is None
                      else eng.statusz()["kv_groups"]["window"])}
    eng.shutdown()
    assert not eng._flight
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_ahead_and_depth_0_serve_the_same(kind, request):
    ahead = _serve(kind, request, hold=False)
    held = _serve(kind, request, hold=True)
    news = KINDS[kind][3]
    assert ahead["status"] == held["status"] == ["finished"] * len(news)
    assert [len(t) for t in ahead["tokens"]] == list(news)
    assert ahead["tokens"] == held["tokens"]
    assert ahead["logprobs"] == held["logprobs"]
    assert ahead["top"] == held["top"]
    # the snapshots a caller saw: wherever both runs showed a request at
    # the same number of tokens, they showed the same positions, the same
    # cache rows and the same state (a pool one update ahead of the
    # tokens would differ here)
    common = sorted(set(ahead["seen"]) & set(held["seen"]))
    assert len(common) >= len(news)
    for key in common:
        (la, ra), (lh, rh) = ahead["seen"][key], held["seen"][key]
        assert la == lh
        for a, h in zip(ra, rh, strict=True):
            np.testing.assert_array_equal(a, h)
    # how the passes were enqueued
    order, horder = ahead["order"], held["order"]
    assert horder["ahead"] == 0
    if kind == "speculative":
        assert order["ahead"] == 0 and set(order["settled"]) == {"spec"}
    else:
        assert order["ahead"] > 0
        assert set(order["settled"]) <= {"idle_start", "reader", "preempt"}
        # every stats() between calls read a pass that HAD been enqueued
        # behind another (but the last, which has none behind it): the
        # reader never cost the loop its depth
        assert 2 * order["ahead"] >= sum(order["settled"].values())
    # ... and the run showed what its kind is here for
    show = KINDS[kind][5]
    if show == "chunks":
        assert max(ahead["passes"]) >= 3
    elif show == "cached":
        assert ahead["cached"] == held["cached"] and max(ahead["cached"]) >= 16
    elif show == "preempt":
        assert ahead["stats"].preemptions > 0 and held["stats"].preemptions > 0
        assert order["settled"].get("preempt", 0) > 0
    elif show == "state":
        assert any(len(rows) == 4 for _, rows in ahead["seen"].values())
    elif show == "window":
        assert ahead["window"]["freed"] == held["window"]["freed"] > 0
        assert ahead["window"]["in_use"] == 0


def test_run_stream_and_shutdown_leave_nothing_unread(gpt):
    net, params = gpt
    eng = mx.serve.Engine(params, symbol=net, **GEO)
    a, b = (eng.submit(p, max_new_tokens=6) for p in _prompts((9, 12)))
    assert eng.step() == 1               # the call reads ONE pass ...
    assert len(eng._flight) == 1 and eng.has_work()   # ... the next is out
    assert len(a.tokens) == 1 and a.cache_len == 9 and a.flight_tokens == 1
    assert list(eng.stream(a)) == a.tokens and len(a.tokens) == 6
    eng.run()
    assert not eng._flight and len(b.tokens) == 6
    c = eng.submit(_prompts((7,))[0], max_new_tokens=5)
    eng.step()
    assert eng._flight
    unread = c.flight_tokens
    eng.shutdown()                       # reads, then cancels
    assert not eng._flight and len(c.tokens) == 1 + unread
    assert c.status == "cancelled"


def test_a_reader_between_calls_carries_its_tokens_to_the_next_step(gpt):
    net, params = gpt
    eng = mx.serve.Engine(params, symbol=net, **GEO)
    req = eng.submit(_prompts((9,))[0], max_new_tokens=5)
    total = eng.step()
    eng.statusz()                        # reads the pass behind
    assert len(req.tokens) == 2 and not eng._flight
    while eng.has_work():
        total += eng.step()
    assert total == 5 == len(req.tokens)
    assert eng.statusz()["step_order"]["settled"].get("reader") == 1
    eng.shutdown()


def _programs_for(lens, geo):
    """The manifest a benchmark cell warms (``serve_cell.programs_for``'s
    rule for whole-prompt prefills): these kinds and no others."""
    ladder = engine_mod.Engine._bucket_ladder(geo["max_batch"])
    return ([{"kind": "decode", "bucket": b} for b in ladder]
            + [{"kind": "prefill", "bucket": b} for b in sorted(
                {engine_mod._next_bucket(n, geo["max_model_len"])
                 for n in lens})])


def test_warm_run_compiles_nothing_and_counts_its_order(gpt, monkeypatch):
    """After ``warmup()`` of a ``programs_for``-style manifest a run that
    goes idle, restarts, changes decode bucket and admits during decode
    compiles nothing, runs no kind beyond prefill / chunk / decode, and
    enqueues every pass behind another but the two that start from idle."""
    import jax.monitoring

    net, params = gpt
    pre = mx.serve.Engine(params, symbol=net, **GEO)
    pre.submit(_prompts((5,))[0], max_new_tokens=2)
    pre.run()                            # the process's own jits (key split)
    pre.shutdown()
    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    eng = mx.serve.Engine(params, symbol=net, **GEO)
    lens = (9, 14, 6, 11)
    manifest = _programs_for(lens, GEO)
    assert eng.warmup(manifest) == len(manifest)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event == BACKEND_COMPILE else None)
    prompts = _prompts(lens)
    first = [eng.submit(p, max_new_tokens=n)
             for p, n in zip(prompts[:2], (12, 9))]
    for _ in range(4):                   # two rows decode: bucket 2 ...
        eng.step()
    late = eng.submit(prompts[2], max_new_tokens=6)   # admitted beside them
    eng.run()                            # ... 3 rows: bucket 4, then 2, 1
    assert not eng.has_work() and not eng._flight     # idle
    again = eng.submit(prompts[3], max_new_tokens=5)  # the restart
    eng.run()
    assert [len(r.tokens) for r in first + [late, again]] == [12, 9, 6, 5]
    assert compiles == []
    ran = {(e["kind"], int(e["bucket"])) for e in eng.manifest()}
    assert {k for k, _ in ran} == {"prefill", "decode"}
    assert ran <= {(e["kind"], e["bucket"]) for e in manifest}
    assert {b for k, b in ran if k == "decode"} == {1, 2, 4}
    order = eng.statusz()["step_order"]
    assert order["settled"] == {"idle_start": 2}
    assert order["ahead"] == sum(
        1 for e in eng._sprof.recent(500) if e["prefills"] or e["decodes"]) - 2
    eng.shutdown()


def test_a_sampled_step_is_enqueued_with_nothing_unread(gpt, monkeypatch):
    """A dispatch the perf sampler times blocks on its outputs: that pass,
    and the one behind it, start with nothing unread; the tokens are those
    of an engine that samples nothing."""
    net, params = gpt

    def serve():
        eng = mx.serve.Engine(params, symbol=net, **GEO)
        reqs = [eng.submit(p, max_new_tokens=9) for p in _prompts((9, 12))]
        eng.run()
        order = eng.statusz()["step_order"]
        eng.shutdown()
        return [list(r.tokens) for r in reqs], order

    plain, order = serve()
    assert "perf_sample" not in order["settled"]
    monkeypatch.setenv("MXTPU_PERF_ATTRIB_SAMPLE", "4")
    sampled, order = serve()
    assert sampled == plain
    assert order["settled"]["perf_sample"] >= 2 and order["ahead"] > 0
