"""Disaggregated prefill/decode serving (mxnet_tpu/fleet, ISSUE 13).

Role-split replicas with content-keyed KV-block handoff over the wire:
``BlockManager.export_blocks``/``import_blocks`` unit semantics (chain
verification, dedup, truncation-degrades), the replica role surface
(``/generate`` on a prefill replica answers a handoff envelope,
``/handoff`` on a decode replica ingests it into the host tier), the
router's prefill→decode orchestration (role-aware least-loaded pick,
``/handoff_probe`` dedup, deadline/trace propagation), and the chaos
matrix — handoff drop, handoff delay past the router timeout,
decode-replica kill mid-handoff with supervisor respawn — every arm
byte-identical to a role="both" fleet.  Composition gates: handoff +
int8 KV + tp=2 + prefix sharing.

Everything is CPU-deterministic and in-process (the test_fleet.py
recipe: real HTTP replicas over real engines, no subprocesses); the
measured A/B contract lives in test_bench_contract-style slow tier
against ``tools/fleet_bench.py --disagg``.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu.fleet import (DEAD, FaultInjector, ReplicaServer, Router,
                             Supervisor)
from mxnet_tpu.serve import BlockManager, HostKVPool

VOCAB = 53
POOL = 1 << 22


@pytest.fixture(scope="module")
def model():
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


def _prompts(n, seed=7, lo=10, hi=20, shared_prefix=0):
    rng = np.random.RandomState(seed)
    out = []
    prefix = rng.randint(0, VOCAB, (shared_prefix,)) if shared_prefix \
        else None
    for _ in range(n):
        p = rng.randint(0, VOCAB,
                        (rng.randint(lo, hi),)).astype(np.int32)
        if prefix is not None:
            p[:shared_prefix] = prefix
        out.append(p)
    return out


def _reference_tokens(model, prompts, max_new, **kw):
    eng = _engine(model, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    out = [list(r.tokens) for r in reqs]
    eng.shutdown()
    return out


@pytest.fixture
def fleet_cleanup():
    items = []
    yield items
    for obj in reversed(items):
        try:
            obj.stop()
        except Exception:
            pass


def _disagg_fleet(model, fleet_cleanup, n_decode=2, router_kw=None,
                  decode_kw=None, prefill_kw=None, decode_rep_kw=None):
    """1 prefill + ``n_decode`` decode replicas + a scraped router."""
    pre = ReplicaServer(_engine(model, **(prefill_kw or {})),
                        replica_id="pre", role="prefill").start()
    fleet_cleanup.append(pre)
    eng_kw = dict(host_kv_bytes=POOL)
    eng_kw.update(decode_kw or {})
    decs = []
    for i in range(n_decode):
        rep_kw = (decode_rep_kw or {}).get(i, {})
        rep = ReplicaServer(_engine(model, **eng_kw),
                            replica_id=f"dec{i}", role="decode",
                            **rep_kw).start()
        fleet_cleanup.append(rep)
        decs.append(rep)
    kw = dict(scrape_interval_s=0, timeout_s=30, retries=4,
              backoff_s=0.01, backoff_max_s=0.05)
    kw.update(router_kw or {})
    router = Router([pre.url] + [d.url for d in decs], **kw)
    fleet_cleanup.append(router)
    router.scrape()
    return pre, decs, router


# -- export/import units ------------------------------------------------------
def _fake_fetch(store):
    """Offload source over a dict: block id -> deterministic arrays."""
    def fetch(blk):
        return store.setdefault(
            blk, (np.full(8, float(blk), np.float32),
                  np.full(8, float(blk) + 0.5, np.float32)))
    return fetch


def test_export_import_roundtrip_and_chain_verification():
    m = BlockManager(num_blocks=9, block_size=4,
                     host_pool=HostKVPool(4096, block_tokens=4))
    m.set_offload_source(_fake_fetch({}))
    ids = list(range(30, 42))                     # 3 full blocks
    m.allocate("a", 12, token_ids=ids)
    m.note_tokens("a", ids)
    recs = m.export_blocks("a", ids)
    assert len(recs) == 3
    assert recs[0][1] is None                     # root has no parent
    assert recs[1][1] == recs[0][0]               # chain links
    assert recs[0][2] == ids[:4]
    # a finished request's blocks still export (parked published)
    m.free("a", retain=True)
    assert [r[0] for r in m.export_blocks("a", ids)] == \
        [r[0] for r in recs]

    # import into a second manager: all park in its host pool and the
    # next allocate walks them as cached tokens
    m2 = BlockManager(num_blocks=9, block_size=4,
                      host_pool=HostKVPool(4096, block_tokens=4))
    assert m2.import_blocks(recs) == (3, 0, 0)
    assert sorted(m2.has_blocks([r[0] for r in recs])) == \
        sorted(r[0] for r in recs)
    _, cached = m2.allocate("b", 13, token_ids=ids + [99])
    assert cached == 12                           # the whole chain hit
    # re-import of the same chain is a pure dedup
    m3_imported = m2.import_blocks(recs)
    assert m3_imported == (0, 3, 0)


def test_import_rejects_corrupt_and_out_of_chain_records():
    m = BlockManager(num_blocks=9, block_size=4,
                     host_pool=HostKVPool(4096, block_tokens=4))
    m.set_offload_source(_fake_fetch({}))
    ids = list(range(50, 62))
    m.allocate("a", 12, token_ids=ids)
    m.note_tokens("a", ids)
    recs = m.export_blocks("a", ids)

    tgt = BlockManager(num_blocks=9, block_size=4,
                       host_pool=HostKVPool(4096, block_tokens=4))
    # corrupt the middle record's tokens: its key no longer verifies,
    # so the chain stops after record 0 (the tail is unreachable)
    bad = [recs[0],
           (recs[1][0], recs[1][1], [1, 2, 3, 4], recs[1][3]),
           recs[2]]
    assert tgt.import_blocks(bad) == (1, 0, 2)
    assert len(tgt.has_blocks([r[0] for r in recs])) == 1
    # out-of-chain-order records never import
    tgt2 = BlockManager(num_blocks=9, block_size=4,
                        host_pool=HostKVPool(4096, block_tokens=4))
    assert tgt2.import_blocks(recs[1:]) == (0, 0, 2)
    # a record with bytes skipped (dedup probe) that is NOT actually
    # cached here breaks the chain instead of importing a hole
    tgt3 = BlockManager(num_blocks=9, block_size=4,
                        host_pool=HostKVPool(4096, block_tokens=4))
    skipped = [(recs[0][0], None, recs[0][2], None)] + recs[1:]
    assert skipped[0][3] is None
    assert tgt3.import_blocks(skipped) == (0, 0, 3)
    # without a host pool nothing imports (and nothing crashes)
    plain = BlockManager(num_blocks=9, block_size=4)
    assert plain.import_blocks(recs) == (0, 0, 3)


def test_pool_peek_leaves_entry_parked():
    p = HostKVPool(4096, block_tokens=4)
    arrs = (np.full(4, 7.0, np.float32),)
    p.put(b"k", None, arrs)
    got = p.peek(b"k")
    assert got is not None and got[0][0] == 7.0
    assert p.has(b"k") and p.restores == 0        # still parked
    assert p.peek(b"missing") is None


# -- role surface -------------------------------------------------------------
def test_role_validation_and_health_signal(model):
    with pytest.raises(ValueError, match="role"):
        ReplicaServer(_engine(model), role="weird")
    # decode role demands the host tier (records land in it)
    eng = _engine(model)
    with pytest.raises(ValueError, match="host-RAM KV tier"):
        ReplicaServer(eng, role="decode")
    eng.shutdown()
    # default role is "both" and the new fields ride /healthz
    eng = _engine(model)
    rep = ReplicaServer(eng, replica_id="r0")
    assert rep.role == "both"
    h = rep._health()
    assert h["role"] == "both" and h["waiting_handoffs"] == 0
    s = rep._replica_state()
    assert s["role"] == "both"
    assert s["handoff"]["received"] == 0
    eng.shutdown()


def test_wrong_role_is_retriable_503(model, fleet_cleanup):
    pre = ReplicaServer(_engine(model), replica_id="p",
                        role="prefill").start()
    dec = ReplicaServer(_engine(model, host_kv_bytes=POOL),
                        replica_id="d", role="decode").start()
    fleet_cleanup.extend([pre, dec])
    prompt = _prompts(1)[0].tolist()

    def post(url, path, payload):
        req = urllib.request.Request(
            f"{url}{path}", data=json.dumps(payload).encode(),
            method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    code, out = post(dec.url, "/generate",
                     {"prompt": prompt, "max_new_tokens": 4})
    assert code == 503 and out["error"] == "wrong_role"
    assert out["retriable"] is True
    code, out = post(pre.url, "/handoff",
                     {"prompt": prompt, "max_new_tokens": 4,
                      "records": []})
    assert code == 503 and out["error"] == "wrong_role"
    # a prefill replica rejects requests whose FULL length could never
    # be served (it only submits prompt+1 itself)
    code, out = post(pre.url, "/generate",
                     {"prompt": [1] * 40, "max_new_tokens": 30})
    assert code == 400 and out["error"] == "exceeds_max_len"


# -- disaggregated fleet ------------------------------------------------------
def test_disagg_fleet_token_identity_and_dedup(model, fleet_cleanup):
    """The acceptance core: a 1-prefill + 2-decode fleet serves
    byte-identically to an uncontended engine, transferred spans count
    as cached tokens on the decode side, and shared prefixes dedup on
    the wire (the radix key IS the transfer dedup)."""
    prompts = _prompts(5, seed=11, shared_prefix=8)
    refs = _reference_tokens(model, prompts, 8)
    pre, decs, router = _disagg_fleet(model, fleet_cleanup)
    for i, p in enumerate(prompts):
        res = router.generate(p.tolist(), max_new_tokens=8,
                              request_id=f"dg-{i}")
        assert res.tokens == refs[i], f"request {i} diverged"
        assert [h.get("hop") for h in res.hops] == [None, "handoff"]
    pstate = pre._replica_state()
    assert pstate["handoff"]["exported"] == len(prompts)
    assert pstate["handoff"]["bytes_exported"] > 0
    received = imported = deduped = 0
    restored = 0
    for d in decs:
        h = d._replica_state()["handoff"]
        received += h["received"]
        imported += h["blocks_imported"]
        deduped += h["blocks_deduped"]
        restored += d.engine.stats().host_kv_restored_tokens
    assert received == len(prompts)
    assert imported > 0
    # the shared 8-token prefix (2 blocks) dedups once a decode
    # replica has seen it — with 5 prompts over 2 replicas at least
    # one repeat lands somewhere
    assert deduped > 0
    assert restored > 0          # imported chains really restored


def test_disagg_identity_int8_kv_tp2_prefix_sharing(model,
                                                    fleet_cleanup):
    """Composition gate: handoff x int8 KV blocks x tp=2 x shared
    prefixes — byte-identical to a role='both' engine with the same
    formulation (identity is per-formulation, as everywhere)."""
    kw = dict(kv_dtype="int8", tp=2)
    prompts = _prompts(3, seed=13, shared_prefix=8)
    refs = _reference_tokens(model, prompts, 6, **kw)
    pre, decs, router = _disagg_fleet(
        model, fleet_cleanup, n_decode=1,
        prefill_kw=kw, decode_kw=kw)
    for i, p in enumerate(prompts):
        res = router.generate(p.tolist(), max_new_tokens=6,
                              request_id=f"q-{i}")
        assert res.tokens == refs[i], f"request {i} diverged"
    h = decs[0]._replica_state()["handoff"]
    assert h["received"] == 3 and h["blocks_imported"] > 0
    # int8 wire records carry the scale slots: 2 extra arrays
    assert len(decs[0].engine.host_block_spec()) == 4


def test_handoff_idempotency_by_request_id(model, fleet_cleanup):
    prompts = _prompts(1, seed=17)
    [ref] = _reference_tokens(model, prompts, 6)
    pre, (dec,), router = _disagg_fleet(model, fleet_cleanup,
                                        n_decode=1)
    r1 = router.generate(prompts[0].tolist(), max_new_tokens=6,
                         request_id="same-id")
    r2 = router.generate(prompts[0].tolist(), max_new_tokens=6,
                         request_id="same-id")
    assert r1.tokens == r2.tokens == ref
    # at-most-once execution per replica: the decode replica served
    # the id once, the retry came from its done-cache
    assert dec.engine.stats().completed == 1
    assert pre.engine.stats().completed == 1


# -- chaos matrix -------------------------------------------------------------
def test_handoff_drop_degrades_to_recompute(model, fleet_cleanup):
    """MXTPU_FAULT_HANDOFF_DROP: the KV records never arrive — the
    decode replica recomputes from the prompt, tokens byte-identical,
    zero imports, drops counted."""
    prompts = _prompts(3, seed=19)
    refs = _reference_tokens(model, prompts, 8)
    pre, decs, router = _disagg_fleet(
        model, fleet_cleanup, n_decode=2,
        decode_rep_kw={0: dict(handoff_drop=100),
                       1: dict(handoff_drop=100)})
    for i, p in enumerate(prompts):
        res = router.generate(p.tolist(), max_new_tokens=8,
                              request_id=f"dr-{i}")
        assert res.tokens == refs[i], f"request {i} diverged"
    h0 = decs[0]._replica_state()["handoff"]
    h1 = decs[1]._replica_state()["handoff"]
    assert h0["blocks_imported"] + h1["blocks_imported"] == 0
    assert h0["drops"] + h1["drops"] == 3


def test_handoff_delay_times_out_and_rehandoffs_on_sibling(
        model, fleet_cleanup):
    """MXTPU_FAULT_HANDOFF_DELAY past the router's per-hop timeout:
    the handoff hop times out and the router re-sends the payload it
    still holds to the sibling decode replica."""
    prompts = _prompts(2, seed=23)
    refs = _reference_tokens(model, prompts, 6)
    pre, decs, router = _disagg_fleet(
        model, fleet_cleanup, n_decode=2,
        router_kw=dict(timeout_s=1.0),
        decode_rep_kw={0: dict(handoff_delay_s=5.0)})
    saw_timeout = False
    for i, p in enumerate(prompts):
        res = router.generate(p.tolist(), max_new_tokens=6,
                              request_id=f"dl-{i}")
        assert res.tokens == refs[i], f"request {i} diverged"
        saw_timeout = saw_timeout or any(
            h["status"] == "timeout" and h.get("hop") == "handoff"
            for h in res.hops)
    assert saw_timeout, "no handoff ever hit the slow replica"


def test_handoff_payload_corruption_detected(model):
    """Same-length byte corruption (valid keys, valid record sizes —
    the arm the chain hash alone cannot catch) fails the payload
    digest at decode, so wrong K/V can never park under a valid
    content key; the receiver degrades to recompute."""
    import base64

    src_eng = _engine(model)
    pre = ReplicaServer(src_eng, replica_id="src", role="prefill")
    prompt = _prompts(1, seed=43, lo=12, hi=13)[0]
    req = src_eng.submit(prompt, max_new_tokens=1)
    src_eng.run()
    records, nbytes = pre._encode_records(
        src_eng.blocks.export_blocks(req.rid, prompt))
    assert records and nbytes > 0
    dst_eng = _engine(model, host_kv_bytes=POOL)
    dst = ReplicaServer(dst_eng, replica_id="dst", role="decode")
    parsed, _ = dst._decode_records(records)      # clean decode works
    assert parsed[0][3] is not None
    raw = bytearray(base64.b64decode(records[0]["k"]))
    raw[0] ^= 0xFF                                # same length, wrong bytes
    records[0]["k"] = base64.b64encode(bytes(raw)).decode()
    with pytest.raises(ValueError, match="digest"):
        dst._decode_records(records)
    src_eng.shutdown()
    dst_eng.shutdown()


class _InProcHandle:
    def __init__(self, replica):
        self.replica = replica
        self.url = replica.url

    def poll(self):
        return None if self.replica.state != DEAD else 1

    def terminate(self, grace_s=None):
        self.replica.stop()


def test_decode_kill_mid_handoff_rehandoff_and_respawn(
        model, fleet_cleanup):
    """Chaos gate: a decode replica dies mid-handoff (kill fault on
    its first /handoff arrival).  The router re-handoffs to the
    sibling — tokens identical — and the supervisor respawns the dead
    slot, after which it serves handoffs again."""
    prompts = _prompts(4, seed=29)
    refs = _reference_tokens(model, prompts, 8)
    pre = ReplicaServer(_engine(model), replica_id="pre",
                        role="prefill").start()
    fleet_cleanup.append(pre)
    router = Router([pre.url], scrape_interval_s=0, timeout_s=30,
                    retries=4, backoff_s=0.01, backoff_max_s=0.05)
    fleet_cleanup.append(router)
    spawned = []

    def spawn(slot):
        injector = (FaultInjector("kill@1")
                    if slot == 0 and not spawned else None)
        rep = ReplicaServer(
            _engine(model, host_kv_bytes=POOL),
            replica_id=f"dec{slot}-{len(spawned)}", role="decode",
            fault_injector=injector).start()
        fleet_cleanup.append(rep)
        spawned.append(rep)
        return _InProcHandle(rep)

    sup = Supervisor(spawn, 2, router=router, restart_backoff_s=0.0)
    sup.start()
    router.scrape()
    doomed = spawned[0]
    results = [router.generate(p.tolist(), max_new_tokens=8,
                               request_id=f"k-{i}")
               for i, p in enumerate(prompts)]
    for i, res in enumerate(results):
        assert res.tokens == refs[i], f"request {i} diverged"
    assert doomed.state == DEAD, "kill fault never fired"
    assert any(len([h for h in r.hops if h.get("hop") == "handoff"]) > 1
               for r in results), "no re-handoff happened"
    # supervisor respawns the dead slot; its replacement serves
    assert sup.check() == [0]
    router.scrape()
    replacement = spawned[-1]
    assert replacement is not doomed
    res = router.generate(prompts[0].tolist(), max_new_tokens=8,
                          request_id="after-respawn")
    assert res.tokens == refs[0]
    sup.stop()


def test_no_decode_replica_exhausts_cleanly(model, fleet_cleanup):
    """A role-split fleet whose every decode replica is gone fails the
    handoff with NoReplicaAvailable after the retry budget — never a
    hang, never a wrong answer."""
    from mxnet_tpu.fleet import NoReplicaAvailable

    pre, (dec,), router = _disagg_fleet(model, fleet_cleanup,
                                        n_decode=1)
    dec.hard_stop()
    router.scrape()
    with pytest.raises(NoReplicaAvailable, match="handoff"):
        router.generate(_prompts(1)[0].tolist(), max_new_tokens=4,
                        request_id="nd-1")


def test_handoff_deadline_propagates_end_to_end(model, fleet_cleanup):
    """deadline_s spans BOTH hops: a decode side that can only reject
    (draining) exhausts the one budget with PermanentError instead of
    getting a fresh window per re-handoff."""
    from mxnet_tpu.fleet import PermanentError

    pre, (dec,), router = _disagg_fleet(
        model, fleet_cleanup, n_decode=1,
        router_kw=dict(retries=10, backoff_s=0.05, backoff_max_s=0.05))
    dec.drain()
    with pytest.raises(PermanentError, match="exhausted"):
        router.generate(_prompts(1)[0].tolist(), max_new_tokens=4,
                        deadline_s=0.3, request_id="ddl-1")


# -- traces + load signal -----------------------------------------------------
def test_trace_stitches_across_roles(model, tmp_path, monkeypatch,
                                     fleet_cleanup):
    """One trace id spans the prefill hop, the decode hop AND (since
    PR 14) the router's own hop-event line — trace_report --stitch
    sees a single multi-hop request."""
    monkeypatch.setenv("MXTPU_REQUEST_TRACE",
                       str(tmp_path / "trace.jsonl"))
    prompts = _prompts(1, seed=31)
    pre, (dec,), router = _disagg_fleet(model, fleet_cleanup,
                                        n_decode=1)
    res = router.generate(prompts[0].tolist(), max_new_tokens=6,
                          request_id="tr-1", trace_id="disagg-tr-1")
    assert res.trace_id == "disagg-tr-1"
    # both replicas' engines share the process-wide trace file here;
    # stop them so the lines flush
    for rep in (pre, dec):
        rep.stop()
    lines = [json.loads(l) for l in
             (tmp_path / "trace.jsonl").read_text().splitlines()
             if l.strip()]
    hops = [l for l in lines if l.get("trace_id") == "disagg-tr-1"]
    # one line per role plus the router's hop-event line (it writes
    # under the same MXTPU_REQUEST_TRACE opt-in, same trace id)
    assert len(hops) == 3
    assert all(h["status"] == "finished" for h in hops)
    router_lines = [h for h in hops if h.get("replica") == "router"]
    assert len(router_lines) == 1
    router_evs = [e["ev"] for e in router_lines[0]["events"]]
    # the stitched view shows router time: pick + generate hop +
    # the handoff move to the decode replica
    assert "pick" in router_evs and "hop" in router_evs
    assert "handoff" in router_evs
    # the decode hop's admit event is marked as a handoff ingest with
    # the transferred span counted as cached tokens
    admits = [e for h in hops for e in h["events"]
              if e["ev"] == "admitted"]
    handoff_admits = [e for e in admits if e.get("handoff")]
    assert len(handoff_admits) == 1
    assert handoff_admits[0]["cached_tokens"] > 0
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    traces = []
    for h in hops:
        traces.append((h, {}, h["status"], None, True))
    s = trace_report.stitch(traces)
    assert s["requests"] == 1 and s["max_hops"] == 3
    assert s["unresolved"] == []


def test_waiting_handoffs_load_signal(model, fleet_cleanup):
    """waiting_handoffs counts accepted-but-not-admitted ingests in
    /healthz and the router's load score reads it."""
    eng = _engine(model, host_kv_bytes=POOL)
    rep = ReplicaServer(eng, replica_id="wh", role="decode")
    assert rep.waiting_handoffs == 0
    # a queued handoff request (scheduler component of the signal)
    req = eng.submit(_prompts(1)[0], max_new_tokens=2, handoff=True)
    assert eng.scheduler.waiting_handoffs() == 1
    assert rep.waiting_handoffs == 1
    assert rep._health()["waiting_handoffs"] == 1
    eng.run()
    assert req.status == "finished"
    assert rep.waiting_handoffs == 0
    eng.shutdown()
    # the router folds it into the load score
    score_idle = Router._load_score(
        {"max_batch": 4, "queue_depth": 0, "running": 0,
         "kv_utilization": 0.0})
    score_busy = Router._load_score(
        {"max_batch": 4, "queue_depth": 0, "running": 0,
         "waiting_handoffs": 2, "kv_utilization": 0.0})
    assert score_busy > score_idle


def test_role_unset_is_inert_schema(model, fleet_cleanup):
    """MXTPU_FLEET_ROLE unset: role 'both', /generate serves tokens
    directly (no handoff envelope), and the /healthz payload is the
    pre-disaggregation one plus only the new optional fields."""
    assert "MXTPU_FLEET_ROLE" not in os.environ
    rep = ReplicaServer(_engine(model), replica_id="inert").start()
    fleet_cleanup.append(rep)
    router = Router([rep.url], scrape_interval_s=0, timeout_s=30,
                    retries=2)
    router.scrape()
    prompts = _prompts(1, seed=37)
    [ref] = _reference_tokens(model, prompts, 6)
    res = router.generate(prompts[0].tolist(), max_new_tokens=6,
                          request_id="in-1")
    assert res.tokens == ref
    assert [h.get("hop") for h in res.hops] == [None]   # single hop
    with urllib.request.urlopen(f"{rep.url}/healthz",
                                timeout=10) as resp:
        hz = json.loads(resp.read())
    legacy = {"status", "state", "in_flight", "queue_depth", "running",
              "host_kv_utilization"}
    assert legacy <= set(hz)
    # the documented additive fields: the disaggregation role/load
    # signals plus the (size-bounded) routable-cache advertisement
    assert set(hz) - legacy == {"role", "waiting_handoffs",
                                "kv_summary"}
    # the advertisement stays bounded: bloom bitmap of m/8 bytes plus
    # at most top_k truncated-hex keys, whatever the cache holds
    ks = hz["kv_summary"]
    assert ks["bloom"]["m"] // 8 >= len(ks["bloom"]["bits"]) * 3 // 4 - 3
    assert len(ks["top"]) <= 32


# -- process-fleet A/B contract (slow tier) -----------------------------------
@pytest.mark.slow
def test_disagg_bench_contract():
    """The DISAGG_BENCH.json stage contract: complete:true (both arms
    availability 1.0, byte-identical tokens, handoffs flowed) and the
    decode-stall improvement the disaggregation exists for."""
    out = "/tmp/disagg_bench_contract.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet_bench.py"),
         "--disagg", "--json", out],
        capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out) as f:
        rec = json.load(f)
    assert rec["complete"] is True
    assert rec["tokens_identical"] is True
    assert rec["disagg"]["availability"] == 1.0
    assert rec["interleaved"]["availability"] == 1.0
    assert rec["handoff_bytes"] > 0
    assert rec["handoff_dedup_blocks"] > 0
    # timing-based: assert the direction with margin
    assert rec["stall_improvement"] >= 2
