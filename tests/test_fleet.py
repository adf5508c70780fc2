"""Fleet-layer tests (mxnet_tpu/fleet): replica front, retrying
router, supervisor, chaos harness.

Everything tier-1 here is CPU-deterministic and in-process: replicas
are real ``ReplicaServer`` HTTP servers over real engines (tiny model,
shared program cache), the router is the real ``Router``, but no
subprocesses are spawned — a *kill* fault uses the in-process
hard-stop (HTTP socket torn down mid-request, engine abandoned), which
is behaviorally what the router/client observe when a process dies.

The two acceptance gates from ISSUE 8:

* chaos: 3 replicas, a deterministic ``kill@k`` fault kills one
  mid-stream — 100% of client requests complete, token output
  identical to a no-fault run, zero duplicated / zero lost responses
  (idempotency keyed on request id).
* rolling restart: drain-based restart of ALL replicas under client
  load completes with zero rejected client requests.

The process-fleet path (tools/serve_replica.py subprocesses +
tools/fleet_bench.py) is pinned by the slow-tier contract case.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu.fleet import (DEAD, DRAINING, READY, FaultInjector,
                             NoReplicaAvailable, ReplicaServer, Router,
                             Supervisor, parse_fault_spec)
from mxnet_tpu.serve import BlockManager, Scheduler
from mxnet_tpu.serve.scheduler import Request
from mxnet_tpu.telemetry import statusz

VOCAB = 53


@pytest.fixture(scope="module")
def model():
    """Tiny gpt2-style net + params (the test_serve recipe: enough
    weight scale for varied greedy sequences)."""
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(3)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 0.35 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale
                        + (1.0 if name.endswith("gamma") else 0.0)
                        ).astype(np.float32)
    return net, params


def _engine(model, **kw):
    net, params = model
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefills_per_step", 2)
    return mx.serve.Engine(params, symbol=net, **kw)


def _prompts(n, seed=7, lo=6, hi=22):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (rng.randint(lo, hi),)).astype(np.int32)
            for _ in range(n)]


def _reference_tokens(model, prompts, max_new):
    """Uncontended single-engine run: the token-identity oracle."""
    eng = _engine(model)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    out = [list(r.tokens) for r in reqs]
    eng.shutdown()
    return out


def _post(url, path, payload, timeout=30):
    """(status_code, body_dict); HTTP errors surface their JSON body."""
    req = urllib.request.Request(
        f"{url}{path}", data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url, path, timeout=10):
    with urllib.request.urlopen(f"{url}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture
def fleet_cleanup():
    """Collects replicas/routers/supervisors to tear down even when an
    assertion fires mid-test."""
    items = []
    yield items
    for obj in reversed(items):
        try:
            obj.stop()
        except Exception:
            pass


# -- fault spec ---------------------------------------------------------------
def test_fault_spec_grammar():
    faults = parse_fault_spec("kill@5;delay@2:0.25;refuse@3:2;hang@7:30")
    assert [(f.action, f.at) for f in faults] == \
        [("kill", 5), ("delay", 2), ("refuse", 3), ("hang", 7)]
    assert faults[1].arg == 0.25
    assert faults[2].matches(3) and faults[2].matches(4)
    assert not faults[2].matches(5)          # refuse range is [3, 5)
    assert faults[0].matches(5) and not faults[0].matches(6)
    assert parse_fault_spec("") == [] and parse_fault_spec(None) == []
    for bad in ("kill", "boom@3", "kill@0", "kill@x"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)

    inj = FaultInjector("refuse@2;kill@4")
    got = [inj.on_request() for _ in range(4)]
    assert got[0] is None and got[2] is None
    assert got[1].action == "refuse" and got[3].action == "kill"
    assert inj.count == 4 and len(inj.fired) == 2


# -- scheduler satellites -----------------------------------------------------
def test_scheduler_rejects_expired_deadline_at_submit():
    """A deadline that is already over at submit is rejected at
    admission (reason deadline_at_submit), counted in all three views
    like every other rejection."""
    m = BlockManager(num_blocks=9, block_size=4)
    s = Scheduler(m, max_batch=2, max_queue=8, clock=lambda: 0.0)
    dead = s.submit(Request(np.arange(1, 4), 4, deadline_s=0.0))
    assert dead.status == "rejected"
    assert dead.reject_reason == "deadline_at_submit"
    neg = s.submit(Request(np.arange(1, 4), 4, deadline_s=-1.0))
    assert neg.reject_reason == "deadline_at_submit"
    live = s.submit(Request(np.arange(1, 4), 4, deadline_s=5.0))
    assert live.status == "waiting"
    assert s.rejections == 2
    assert s.reject_reasons == {"deadline_at_submit": 2}
    assert s.queue_depth == 1                # rejected ones never queued


def test_scheduler_tenant_fair_share_cap_and_rotation():
    clock = {"now": 0.0}
    m = BlockManager(num_blocks=33, block_size=4)
    s = Scheduler(m, max_batch=4, max_queue=4, max_prefills_per_step=2,
                  clock=lambda: clock["now"], tenant_share=0.5)
    # cap: one tenant may hold at most 0.5 * 4 = 2 waiting slots
    a1 = s.submit(Request(np.arange(1, 5), 2, tenant="abuser"))
    a2 = s.submit(Request(np.arange(1, 5), 2, tenant="abuser"))
    a3 = s.submit(Request(np.arange(1, 5), 2, tenant="abuser"))
    assert a1.status == a2.status == "waiting"
    assert a3.status == "rejected" and a3.reject_reason == "tenant_share"
    # the polite tenant still has queue headroom
    b1 = s.submit(Request(np.arange(1, 5), 2, tenant="polite"))
    assert b1.status == "waiting"
    # round-robin admission: one abuser request, then the polite one —
    # not two abusers first (strict FIFO would admit a1, a2)
    prefills, _ = s.schedule()
    assert [(r.tenant, r.rid) for r in prefills] == \
        [("abuser", a1.rid), ("polite", b1.rid)]
    stats = s.tenant_stats()
    assert stats["abuser"]["rejected"] == 1
    assert stats["abuser"]["submitted"] == 2
    assert stats["polite"]["submitted"] == 1
    # tenant=None and tenant="default" are ONE tenant sharing one cap
    # (an untagged client must not get a second share by mixing them)
    d1 = s.submit(Request(np.arange(1, 5), 2))               # None
    d2 = s.submit(Request(np.arange(1, 5), 2, tenant="default"))
    d3 = s.submit(Request(np.arange(1, 5), 2))
    assert d1.status == d2.status == "waiting"
    assert d3.status == "rejected" and d3.reject_reason == "tenant_share"


def test_engine_tenant_plumbing_and_trace_id(model):
    eng = _engine(model)
    req = eng.submit(_prompts(1)[0], max_new_tokens=4, tenant="acme",
                     trace_id="fleet-abc123")
    assert req.trace_id == "fleet-abc123"    # pre-stamp survives tracing
    eng.run()
    st = eng.stats()
    assert st.tenants["acme"]["completed"] == 1
    assert st.tenants["acme"]["latency_s_mean"] is not None
    assert eng.statusz()["tenants"]["acme"]["completed"] == 1
    eng.shutdown()


# -- replica front ------------------------------------------------------------
def test_replica_roundtrip_idempotency_and_statusz(model, fleet_cleanup):
    prompts = _prompts(1, seed=11)
    [ref] = _reference_tokens(model, prompts, 8)
    rep = ReplicaServer(_engine(model), replica_id="r0").start()
    fleet_cleanup.append(rep)
    assert rep.state == READY
    code, out = _post(rep.url, "/generate",
                      {"prompt": prompts[0].tolist(), "max_new_tokens": 8,
                       "request_id": "req-1", "tenant": "acme"})
    assert code == 200 and out["tokens"] == ref
    assert out["replica"] == "r0" and out["tenant"] == "acme"
    # idempotent retry: same id -> cached response, no recompute
    code, again = _post(rep.url, "/generate",
                        {"prompt": prompts[0].tolist(),
                         "max_new_tokens": 8, "request_id": "req-1"})
    assert code == 200 and again["tokens"] == ref and again["deduped"]
    assert rep.engine.stats().completed == 1
    # statusz carries the routing signal section
    snap = _get(rep.url, "/statusz.json")
    assert snap["replica"]["replica"] == "r0"
    assert snap["replica"]["state"] == "ready"
    assert "queue_depth" in snap["replica"]
    assert "kv_utilization" in snap["replica"]
    # permanent rejection maps to 400 (router must not retry it)
    code, err = _post(rep.url, "/generate",
                      {"prompt": [1] * 60, "max_new_tokens": 30})
    assert code == 400 and err["error"] == "exceeds_max_len"
    assert err["retriable"] is False
    # malformed client inputs are clean 400s, never 500s the router
    # would count as replica transport failures and retry fleet-wide
    for bad in ({"prompt": [], "max_new_tokens": 4},
                {"prompt": [1, 2], "max_new_tokens": 0},
                {"prompt": [1, 2], "max_new_tokens": 4,
                 "deadline_s": "abc"},
                {"max_new_tokens": 4}):
        code, err = _post(rep.url, "/generate", bad)
        assert code == 400 and err["error"] == "bad_request", (bad, err)
        assert err["retriable"] is False
    rep.stop()
    assert rep.engine.params is None          # engine released


def test_drain_finishes_inflight_token_identically(model, fleet_cleanup):
    """Satellite: a draining replica completes its in-flight requests
    with EXACTLY the tokens of an undrained run, rejects new submits
    retriably, and leaves the router's rotation within one scrape
    interval."""
    prompts = _prompts(3, seed=23)
    refs = _reference_tokens(model, prompts, 40)
    rep = ReplicaServer(_engine(model), replica_id="drainee").start()
    fleet_cleanup.append(rep)
    router = Router([rep.url], scrape_interval_s=0.1, timeout_s=30,
                    retries=1)
    fleet_cleanup.append(router)
    router.scrape()
    router.start()

    results = {}

    def client(i):
        code, out = _post(rep.url, "/generate",
                          {"prompt": prompts[i].tolist(),
                           "max_new_tokens": 40,
                           "request_id": f"d-{i}"}, timeout=60)
        results[i] = (code, out)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    # wait until the requests are genuinely in flight, then drain
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and not rep.engine.scheduler.running:
        time.sleep(0.002)
    assert rep.engine.scheduler.running, "requests never started"
    code, out = _post(rep.url, "/drain", {})
    assert code == 200 and out["state"] == DRAINING
    assert rep.engine.scheduler.has_work(), \
        "drain landed after all work finished — test is vacuous"
    # new submits are rejected with a retriable status
    code, rej = _post(rep.url, "/generate",
                      {"prompt": prompts[0].tolist(),
                       "max_new_tokens": 4})
    assert code == 503 and rej["retriable"] is True
    # in-flight requests finish token-identically
    for t in threads:
        t.join(timeout=60)
    for i in range(3):
        code, out = results[i]
        assert code == 200, out
        assert out["tokens"] == refs[i]
    # the router noticed within one scrape interval
    time.sleep(0.3)
    snap = router.snapshot()
    assert snap[0]["state"] == "draining"
    with pytest.raises(NoReplicaAvailable):
        router.generate(prompts[0].tolist(), max_new_tokens=4)
    assert rep.drained()


def test_chaos_kill_mid_stream_all_requests_complete(model, fleet_cleanup):
    """Acceptance gate: 3 replicas, a deterministic kill fault takes
    one down mid-stream; every client request still completes via
    retry-on-sibling with tokens identical to a no-fault run, and the
    request-id ledger shows zero duplicated / zero lost responses."""
    n_req, max_new = 8, 16
    prompts = _prompts(n_req, seed=31)
    refs = _reference_tokens(model, prompts, max_new)

    injector = FaultInjector("kill@2")       # dies at ITS 2nd arrival
    reps = []
    for i in range(3):
        rep = ReplicaServer(
            _engine(model), replica_id=f"c{i}",
            fault_injector=injector if i == 1 else None).start()
        fleet_cleanup.append(rep)
        reps.append(rep)
    router = Router([r.url for r in reps], scrape_interval_s=0,
                    timeout_s=30, retries=4, backoff_s=0.01,
                    backoff_max_s=0.05, breaker_fails=3,
                    breaker_reset_s=5.0)
    router.scrape()

    results = {}
    for i, p in enumerate(prompts):
        res = router.generate(p.tolist(), max_new_tokens=max_new,
                              request_id=f"chaos-{i}")
        # one response per request id: the ledger can never see two
        assert i not in results
        results[i] = res

    assert reps[1].state == DEAD, "kill fault never fired"
    assert injector.fired and injector.fired[0][1].action == "kill"
    assert len(results) == n_req             # zero lost
    for i in range(n_req):
        assert results[i].tokens == refs[i], f"request {i} diverged"
    assert any(r.attempts > 1 for r in results.values()), \
        "no request was retried — the kill was invisible to the test"
    # zero duplicated server-side: live replicas each served every
    # completed id at most once (dedup cache) — total completions of
    # live engines == client responses minus none
    served = sum(r.engine.stats().completed for r in reps if
                 r.state != DEAD)
    assert served >= n_req - 2   # killed replica may have finished some
    # the dead replica's breaker opened or its state went down
    snap = {s["replica"]: s for s in router.snapshot()}
    assert snap["c1"]["consecutive_failures"] >= 1 \
        or snap["c1"]["breaker_open"] or snap["c1"]["state"] == "down"


class _InProcHandle:
    """Supervisor handle over an in-process ReplicaServer (the
    process-free stand-in the supervisor contract allows)."""

    def __init__(self, replica):
        self.replica = replica
        self.url = replica.url

    def poll(self):
        return None if self.replica.state != DEAD else 1

    def terminate(self, grace_s=None):
        self.replica.stop()


def test_rolling_restart_zero_client_rejects(model, fleet_cleanup):
    """Acceptance gate: drain-based rolling restart of ALL replicas
    under client load — zero rejected client requests, token output
    still reference-identical."""
    n_req, max_new = 18, 8
    prompts = _prompts(n_req, seed=41)
    refs = _reference_tokens(model, prompts, max_new)

    def spawn(slot):
        rep = ReplicaServer(_engine(model),
                            replica_id=f"slot{slot}").start()
        fleet_cleanup.append(rep)
        return _InProcHandle(rep)

    router = Router([], scrape_interval_s=0.1, timeout_s=30, retries=6,
                    backoff_s=0.02, backoff_max_s=0.2,
                    breaker_fails=10)
    fleet_cleanup.append(router)
    sup = Supervisor(spawn, 3, router=router, drain_timeout_s=30)
    sup.start()
    router.scrape()
    router.start()
    first_gen = set(sup.urls())

    results, failures = {}, {}

    def load():
        for i, p in enumerate(prompts):
            try:
                results[i] = router.generate(
                    p.tolist(), max_new_tokens=max_new,
                    request_id=f"roll-{i}")
            except Exception as e:           # any client-visible failure
                failures[i] = repr(e)
            time.sleep(0.01)

    t = threading.Thread(target=load, daemon=True)
    t.start()
    sup.rolling_restart()
    t.join(timeout=120)
    assert not failures, f"client saw failures: {failures}"
    assert len(results) == n_req
    for i in range(n_req):
        assert results[i].tokens == refs[i]
    # every slot was really replaced
    assert not (set(sup.urls()) & first_gen)
    sup.stop()


def test_router_circuit_breaker_opens_and_half_opens(model,
                                                     fleet_cleanup):
    clock = {"now": 0.0}
    rep = ReplicaServer(_engine(model), replica_id="live").start()
    fleet_cleanup.append(rep)
    # a port that refuses connections: bind-and-close
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_url = f"http://127.0.0.1:{s.getsockname()[1]}"
    s.close()
    router = Router([dead_url, rep.url], scrape_interval_s=0,
                    timeout_s=5, retries=4, backoff_s=0.0,
                    backoff_max_s=0.0, breaker_fails=2,
                    breaker_reset_s=10.0, clock=lambda: clock["now"],
                    sleep=lambda s_: None)
    prompt = _prompts(1)[0].tolist()
    for i in range(3):
        res = router.generate(prompt, max_new_tokens=2,
                              request_id=f"cb-{i}")
        assert res.tokens
    snap = {x["url"]: x for x in router.snapshot()}
    assert snap[dead_url]["breaker_open"], snap
    # with the breaker open the dead replica is never attempted
    res = router.generate(prompt, max_new_tokens=2, request_id="cb-x")
    assert res.attempts == 1
    # past the reset window, a half-open probe may pick it again
    clock["now"] = 11.0
    assert not {x["url"]: x for x in
                router.snapshot()}[dead_url]["breaker_open"]
    res = router.generate(prompt, max_new_tokens=2, request_id="cb-y")
    assert res.tokens                        # probe fails -> sibling
    # the failed probe RE-OPENS the breaker (it must not retire after
    # one cycle and hand the dead replica a first attempt per request)
    assert {x["url"]: x for x in
            router.snapshot()}[dead_url]["breaker_open"]


def test_router_timeout_retries_hung_replica(model, fleet_cleanup):
    hung = ReplicaServer(_engine(model), replica_id="hung",
                         fault_injector=FaultInjector("hang@1:20")
                         ).start()
    live = ReplicaServer(_engine(model), replica_id="live2").start()
    fleet_cleanup.extend([hung, live])
    router = Router([hung.url, live.url], scrape_interval_s=0,
                    timeout_s=0.5, retries=3, backoff_s=0.01,
                    backoff_max_s=0.05)
    router.scrape()
    prompts = _prompts(1, seed=51)
    [ref] = _reference_tokens(model, prompts, 6)
    # the repeated prompt hits the live replica's prefix cache and runs
    # the suffix (chunk) program: compile it here, into the process-wide
    # program cache, not inside the router's 0.5 s timeout (a 0.3 s XLA
    # compile on a CPU shared with five other test workers outlasts it,
    # and then the LIVE replica is the one that times out)
    warm = _engine(model)
    for _ in range(2):
        warm.submit(prompts[0], max_new_tokens=6)
        warm.run()
    warm.shutdown()
    # drive requests until one lands on the hung replica first (the
    # rr tiebreak guarantees it within two requests)
    saw_timeout = False
    for i in range(3):
        res = router.generate(prompts[0].tolist(), max_new_tokens=6,
                              request_id=f"hang-{i}")
        assert res.tokens == ref
        saw_timeout = saw_timeout or any(
            h["status"] == "timeout" for h in res.hops)
    assert saw_timeout, "no attempt ever hit the hung replica"


def test_router_retries_queue_full_on_sibling(model, fleet_cleanup):
    tiny = ReplicaServer(_engine(model, max_queue=1, max_batch=1),
                         replica_id="tiny").start()
    big = ReplicaServer(_engine(model), replica_id="big").start()
    fleet_cleanup.extend([tiny, big])
    router = Router([tiny.url, big.url], scrape_interval_s=0,
                    timeout_s=30, retries=4, backoff_s=0.01,
                    backoff_max_s=0.02)
    prompts = _prompts(6, seed=61)
    results = {}
    threads = []

    def client(i):
        results[i] = router.generate(prompts[i].tolist(),
                                     max_new_tokens=8,
                                     request_id=f"qf-{i}")

    for i in range(6):
        th = threading.Thread(target=client, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=60)
    assert len(results) == 6
    refs = _reference_tokens(model, prompts, 8)
    for i in range(6):
        assert results[i].tokens == refs[i]


def test_router_deadline_is_end_to_end(model, fleet_cleanup):
    """deadline_s is one budget across ALL retry hops — it decays per
    attempt and an exhausted deadline stops retrying with a permanent
    error instead of granting each sibling a fresh window."""
    from mxnet_tpu.fleet import PermanentError

    rep = ReplicaServer(_engine(model), replica_id="dl").start()
    fleet_cleanup.append(rep)
    rep.drain()                              # every hop: 503 draining
    router = Router([rep.url], scrape_interval_s=0, timeout_s=5,
                    retries=10, backoff_s=0.05, backoff_max_s=0.05)
    with pytest.raises(PermanentError, match="exhausted"):
        router.generate(_prompts(1)[0].tolist(), max_new_tokens=4,
                        deadline_s=0.15, request_id="dl-1")


def test_supervisor_crash_restart_with_backoff(model, fleet_cleanup):
    clock = {"now": 0.0}
    spawned = []

    def spawn(slot):
        rep = ReplicaServer(_engine(model),
                            replica_id=f"s{slot}-{len(spawned)}").start()
        fleet_cleanup.append(rep)
        spawned.append(rep)
        return _InProcHandle(rep)

    sup = Supervisor(spawn, 1, restart_backoff_s=1.0,
                     restart_backoff_max_s=8.0,
                     clock=lambda: clock["now"], sleep=lambda s: None)
    sup.start()
    assert len(spawned) == 1
    assert sup.check() == []                 # healthy: nothing to do
    spawned[-1].hard_stop()                  # crash
    assert sup.check() == [0]                # restarted immediately
    assert len(spawned) == 2
    spawned[-1].hard_stop()                  # crashes again...
    assert sup.check() == []                 # ...but inside backoff
    clock["now"] = 1.1
    # a slot mid-drain_and_restart is the supervisor's OWN doing: the
    # crash monitor must not double-spawn it
    with sup._lock:
        sup._rolling.add(0)
    assert sup.check() == []
    with sup._lock:
        sup._rolling.discard(0)
    assert sup.check() == [0]                # backoff elapsed
    assert len(spawned) == 3
    with sup._lock:
        assert sup._restarts[0] == 2
    sup.note_healthy(0)
    with sup._lock:
        assert sup._restarts[0] == 0
    sup.stop()


def test_kill_fault_fires_even_on_dedup_cache_hit(model, fleet_cleanup):
    """Deterministic chaos contract: the arrival the spec kills is
    dead even when it would have been answered from the idempotency
    cache — the client sees a disconnect, never the cached response."""
    rep = ReplicaServer(_engine(model), replica_id="kd",
                        fault_injector=FaultInjector("kill@2")).start()
    fleet_cleanup.append(rep)
    prompt = _prompts(1, seed=71)[0].tolist()
    code, out = _post(rep.url, "/generate",
                      {"prompt": prompt, "max_new_tokens": 4,
                       "request_id": "same-id"})
    assert code == 200
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _post(rep.url, "/generate",
              {"prompt": prompt, "max_new_tokens": 4,
               "request_id": "same-id"})
    assert rep.state == DEAD


def test_prestamped_trace_id_rejection_still_writes_jsonl(
        model, tmp_path, monkeypatch):
    """A fleet-routed request rejected at the engine's own guard (the
    tracer never saw a submit) must still close its timeline in the
    JSONL export — keyed on the tracer's sampling mark, not on whether
    a trace id was pre-stamped by the router."""
    trace_file = tmp_path / "trace.jsonl"
    monkeypatch.setenv("MXTPU_REQUEST_TRACE", str(trace_file))
    eng = _engine(model)
    req = eng.submit([1] * 60, max_new_tokens=30,
                     trace_id="fleet-prestamp")
    assert req.status == "rejected"
    assert req.reject_reason == "exceeds_max_len"
    eng.shutdown()
    lines = [json.loads(l) for l in
             trace_file.read_text().splitlines() if l.strip()]
    assert len(lines) == 1
    assert lines[0]["trace_id"] == "fleet-prestamp"
    assert lines[0]["status"] == "rejected"
    assert [e["ev"] for e in lines[0]["events"]] == \
        ["submitted", "rejected"]


# -- telemetry /healthz satellite ---------------------------------------------
def test_telemetry_healthz_endpoint_is_cheap():
    from mxnet_tpu import telemetry

    calls = {"statusz": 0}
    sname = statusz.register("expensive.provider",
                             lambda: calls.__setitem__(
                                 "statusz", calls["statusz"] + 1) or {})
    hname = statusz.register_health("unit.h", lambda: {"status": "ok",
                                                       "n": 1})
    server = telemetry.serve_http(telemetry.registry(), 0)
    try:
        port = server.server_address[1]
        hz = _get(f"http://127.0.0.1:{port}", "/healthz")
        assert hz["status"] == "ok"
        assert hz["checks"]["unit.h"]["n"] == 1
        # the whole point: /healthz never runs the statusz providers
        assert calls["statusz"] == 0
        # a non-ok provider propagates to the top-level status
        statusz.register_health("unit.drain",
                                lambda: {"status": "draining"})
        hz = _get(f"http://127.0.0.1:{port}", "/healthz")
        assert hz["status"] == "draining"
        # a raising provider degrades to error, never a 500 page
        statusz.register_health("unit.broken",
                                lambda: 1 / 0)
        hz = _get(f"http://127.0.0.1:{port}", "/healthz")
        assert hz["checks"]["unit.broken"]["status"] == "error"
    finally:
        statusz.unregister(sname)
        statusz.unregister_health(hname)
        statusz.unregister_health("unit.drain")
        statusz.unregister_health("unit.broken")
        server.shutdown()


def test_trace_stitching_groups_by_trace_id():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    def rec(tid, status, reason=None):
        return ({"trace_id": tid, "status": status}, {}, status, reason,
                True)

    traces = [rec("t1", "rejected", "queue_full"), rec("t1", "finished"),
              rec("t2", "finished"), rec("t3", "cancelled"),
              rec("t4", "rejected", "exceeds_max_len")]
    s = trace_report.stitch(traces)
    assert s["requests"] == 4
    assert s["multi_hop"] == 1 and s["max_hops"] == 2
    # t3 vanished mid-retry; t4 got a CORRECT permanent 400 — resolved
    assert s["unresolved"] == ["t3"]


def test_replica_and_fleet_env_knobs_documented():
    """Every MXTPU_FLEET_*/MXTPU_FAULT_* knob the fleet reads must have
    an env_vars.md row (the check_env_docs gate covers this globally;
    this pin makes the fleet subset explicit)."""
    with open(os.path.join(REPO, "docs", "env_vars.md")) as f:
        doc = f.read()
    for var in ("MXTPU_FAULT_SPEC", "MXTPU_FLEET_TIMEOUT",
                "MXTPU_FLEET_ROLE", "MXTPU_FAULT_HANDOFF_DELAY",
                "MXTPU_FAULT_HANDOFF_DROP",
                "MXTPU_FLEET_RETRIES", "MXTPU_FLEET_BACKOFF",
                "MXTPU_FLEET_BACKOFF_MAX", "MXTPU_FLEET_BREAKER_FAILS",
                "MXTPU_FLEET_BREAKER_RESET",
                "MXTPU_FLEET_SCRAPE_INTERVAL",
                "MXTPU_FLEET_RESTART_BACKOFF",
                "MXTPU_FLEET_RESTART_BACKOFF_MAX",
                "MXTPU_FLEET_DRAIN_TIMEOUT",
                "MXTPU_SERVE_TENANT_SHARE"):
        assert var in doc, f"{var} missing from docs/env_vars.md"


# -- process fleet contract (slow tier) ---------------------------------------
@pytest.mark.slow
def test_fleet_bench_contract():
    """The FLEET_BENCH.json stage contract: complete:true and
    availability == 1.0 on the CPU smoke (3 real replica processes,
    one injected kill, rolling restart)."""
    out = "/tmp/fleet_bench_contract.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_bench.py"),
         "--requests", "12", "--rate", "6", "--kill-at", "3",
         "--restart-requests", "6", "--json", out],
        capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out) as f:
        rec = json.load(f)
    assert rec["complete"] is True
    assert rec["availability"] == 1.0
    assert rec["restart_rejects"] == 0
    assert rec["token_consistent"] is True
    assert rec["crash_restarts"] >= 1
    assert rec["p99_added_router_ms"] is not None
