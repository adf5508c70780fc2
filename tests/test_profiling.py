"""Continuous profiling plane (ISSUE 20): the per-step host-overhead
decomposition recorder, the on-demand ``/profilez`` capture window, the
SLO-triggered capture, and the stitched fleet timeline.

The acceptance gates pinned here:

* ring accounting: every step's phase seconds sum EXACTLY to its wall
  time (the lap/cursor model attributes each elapsed nanosecond to one
  phase), the ring stays bounded, and the three surfaces — engine
  statusz, ``mxtpu_step_phase_seconds`` metrics, flight dumps — agree;
* ``POST /profilez``: happy path produces a real device-trace artifact,
  a concurrent second POST gets a clean 409 (never a breaker-tripping
  500), back-to-back windows are rate-limited (429 + retry_after_s),
  and stopping the replica mid-window ends the capture cleanly;
* an SLO fast-burn alert triggers a short capture on the offending
  replica and the flight dump carries the capture id;
* ``tools/timeline_report.py`` stitches router hops, replica trace
  lines and step rings into a well-formed Chrome trace with zero
  unresolved hops under ``--check``;
* inertness: ``MXTPU_STEP_PROFILE=0`` installs the NOOP recorder and
  tokens are byte-identical either way.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import mxnet_tpu as mx
from mxnet_tpu import profiler as profiler_mod
from mxnet_tpu import telemetry
from mxnet_tpu.fleet import (FaultInjector, FleetCollector, ReplicaServer,
                             Router, SLOEvaluator, parse_slo_spec)
from mxnet_tpu.telemetry import profiling

VOCAB = 53


@pytest.fixture(scope="module")
def model():
    """Tiny gpt2-style net + params (the test_serve recipe)."""
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


def _run(eng, prompt, max_new=4):
    req = eng.submit(prompt, max_new_tokens=max_new)
    while not req.done:
        eng.step()
    return req


def _get(url, path, timeout=10):
    with urllib.request.urlopen(f"{url}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


def _post(url, path, payload, timeout=30):
    import urllib.error

    req = urllib.request.Request(
        f"{url}{path}", data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture
def fleet_cleanup():
    items = []
    yield items
    for obj in reversed(items):
        try:
            obj.stop()
        except Exception:
            pass


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.reset()


def _wait_capture(url, cap_id, timeout=20):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        meta = _get(url, f"/profilez/{cap_id}")
        if meta.get("state") in ("done", "failed"):
            return meta
        time.sleep(0.05)
    return meta


# -- ring accounting (pure unit: fake clock) ----------------------------------
def test_step_profiler_phases_sum_to_wall():
    clock = {"now": 100.0}

    def tick():
        return clock["now"]

    sp = profiling.StepProfiler(clock=tick, ring=4)
    # begin() opens "schedule"; each enter() closes the open phase at
    # the instant it opens the next; commit() closes the last
    phases = [("schedule", 0.010), ("prefill_dispatch", 0.002),
              ("device_wait", 0.050), ("host_sync", 0.001),
              ("decode_dispatch", 0.004), ("device_wait", 0.030),
              ("callbacks", 0.003)]
    for step in range(6):
        sp.begin(step)
        for i, (phase, dt) in enumerate(phases):
            if i:
                sp.enter(phase)
            clock["now"] += dt
        sp.commit(emitted=2, prefills=1, decodes=1)
    # ring bounded at 4; totals keep counting all 6 steps
    entries = sp.recent()
    assert len(entries) == 4
    assert [e["step"] for e in entries] == [2, 3, 4, 5]
    wall = 0.1
    for e in entries:
        assert e["wall_s"] == pytest.approx(wall, abs=1e-12)
        # the accounting identity: phases sum EXACTLY to the wall
        assert sum(e["phases"].values()) == pytest.approx(
            e["wall_s"], rel=1e-12)
        # a phase entered twice accumulates (two device waits)
        assert e["phases"]["device_wait"] == pytest.approx(0.08)
        assert e["phases"]["callbacks"] == pytest.approx(0.003)
        assert e["emitted"] == 2
    st = sp.statusz()
    assert st["enabled"] is True and st["steps"] == 6
    assert st["wall_s"] == pytest.approx(6 * wall)
    assert sum(st["totals_s"].values()) == pytest.approx(st["wall_s"])
    fr = st["fractions"]
    assert set(fr) == set(profiling.PHASES)
    assert sum(fr.values()) == pytest.approx(1.0)
    assert fr["device_wait"] == pytest.approx(0.8)
    # the stitching anchor rides along
    assert set(st["clock_anchor"]) == {"perf", "epoch"}
    assert sp.summary()["steps"] == 6


def test_step_profiler_env_knobs(monkeypatch):
    monkeypatch.setenv(profiling.ENV_ENABLE, "0")
    assert profiling.make_step_profiler() is profiling.NOOP_STEP_PROFILER
    noop = profiling.make_step_profiler()
    noop.begin(1)
    noop.enter("device_wait")
    noop.note(tokens=3)
    noop.commit()
    assert noop.tracing is False
    assert noop.recent() == [] and noop.summary() is None
    assert noop.statusz() == {"enabled": False}
    monkeypatch.setenv(profiling.ENV_ENABLE, "1")
    monkeypatch.setenv(profiling.ENV_RING, "7")
    live = profiling.make_step_profiler()
    assert live.enabled and live._ring.maxlen == 7


# -- the pass record (pure unit: fake clock, fake readiness) -------------------
class _Lead:
    """A part's output as the pass timer sees it: ``is_ready()`` says what
    the test set, and counts the polls."""

    def __init__(self):
        self.ready = False
        self.polls = 0

    def is_ready(self):
        self.polls += 1
        return self.ready


class _Driver:
    """Drives a ``StepProfiler`` by hand on a fake clock in milliseconds."""

    def __init__(self):
        self.now = 0.0
        self.heard = []
        self.sp = profiling.StepProfiler(
            clock=lambda: self.now / 1e3, ring=16,
            on_pass=lambda *a: self.heard.append(a))

    def at(self, ms, call, *args, **kw):
        self.now = ms
        return getattr(self.sp, call)(*args, **kw)

    def part(self, kind, bucket=4, first=False, reason=None):
        lead = _Lead()
        self.sp.reason = reason
        return lead, self.sp.enqueued(kind, bucket, 3, lead, first)


def _passes():
    return {round(s[3] * 1e3, 6): s for s in
            telemetry.tracer().spans(prefix="serve.pass")}


def test_pass_stamps_tile_device_time(tel):
    """Five parts over five calls: running ahead, a host late past the
    device (the caller's turn), a pass read with nothing behind it, the
    engine emptied, a start from idle.  Every part runs from max(its
    enqueue, the end of the part before) to its end, and the parts and
    the idle between them tile the device's timeline."""
    d = _Driver()
    d.at(0, "begin", 1)
    d.at(1, "enter", "prefill_dispatch")
    la, a = d.part("prefill", 16, first=True, reason="idle_start")
    d.at(3, "enter", "schedule")                  # a enqueued at 3
    d.at(4, "enter", "decode_dispatch")
    lb, b = d.part("decode", first=True)
    d.at(6, "wait", "serve.prefill", a)           # b enqueued at 6
    d.at(20, "enter", "host_sync")                # a read: blocked 14 ms
    d.at(21, "enter", "callbacks")
    d.at(22, "commit", 1, work_left=1)
    d.at(23, "begin", 2)
    d.at(24, "enter", "decode_dispatch")
    lc, c = d.part("decode")
    d.at(27, "wait", "serve.decode", b)           # c enqueued at 27
    lb.ready = True
    d.at(30, "enter", "host_sync")
    d.at(31, "enter", "callbacks")
    d.at(32, "commit", 1, work_left=1)
    lc.ready = True                               # done in the caller's turn
    d.at(50, "begin", 3)
    assert c.dry == (0.032, 0.050, "caller")
    d.at(51, "enter", "decode_dispatch")
    ld, dd = d.part("decode")
    d.at(53, "wait", "serve.decode", c)
    d.at(53.05, "enter", "host_sync")             # c read: did not block
    d.at(54, "enter", "callbacks")
    d.at(55, "commit", 1, work_left=1)
    d.at(56, "begin", 4)
    d.at(56.5, "wait", "serve.decode", dd)        # nothing enqueued behind
    d.at(66, "enter", "host_sync")
    d.at(67, "enter", "callbacks")
    d.at(68, "commit", 1, work_left=0)            # the engine is empty
    d.at(200, "begin", 5)
    d.at(201, "enter", "prefill_dispatch")
    le, e = d.part("prefill", 16, reason="idle_start")
    d.at(203, "wait", "serve.prefill", e)
    d.at(240, "enter", "host_sync")
    d.at(241, "commit", 1, work_left=0)
    got = _passes()
    assert sorted(got) == [3, 20, 30, 53, 203]
    want = {  # start: (end, exact, first_run, idle_before, idle_max, cause)
        3: (20, True, True, 0, 0, None),
        20: (30, True, True, 0, 0, None),
        30: (50, False, False, 0, 0, None),
        53: (66, True, False, 3, 21, "caller"),
        203: (240, True, False, 137, 137, "no_work")}
    for start, (end, exact_, first, lo, hi, cause) in want.items():
        s = got[start]
        args = s[5]
        assert s[4] * 1e3 == pytest.approx(end)
        assert (args["exact"], args["first_run"], args["idle_cause"]) == \
            (exact_, first, cause), start
        assert args["idle_before_s"] * 1e3 == pytest.approx(lo)
        assert args["idle_max_s"] * 1e3 == pytest.approx(hi)
    device = sum(s[4] - s[3] for s in got.values())
    idle = sum(s[5]["idle_before_s"] for s in got.values())
    assert (device + idle) * 1e3 == pytest.approx(240 - 3)
    assert [s[5]["dispatch_s"] * 1e3 for s in got.values()] == \
        pytest.approx([2, 2, 3, 2, 2])
    assert [s[5]["step"] for s in got.values()] == [1, 1, 2, 3, 5]
    assert [s[5]["reason"] for s in got.values()] == \
        ["idle_start", None, None, None, "idle_start"]
    # the same parts as the cost table hears them, and as counters
    assert [(k, b, round(t * 1e3, 6), x) for k, b, t, x in d.heard] == [
        ("prefill", 16, 17, True), ("decode", 4, 10, True),
        ("decode", 4, 20, False), ("decode", 4, 13, True),
        ("prefill", 16, 37, True)]
    snap = telemetry.snapshot()["metrics"]

    def counter(name):
        return {tuple(s["labels"].values())[0]: s["value"]
                for s in snap[name]["samples"]}

    assert counter("mxtpu_serve_device_seconds_total") == pytest.approx(
        {"prefill": 0.054, "decode": 0.043})
    assert counter("mxtpu_serve_device_idle_seconds_total") == \
        pytest.approx({"caller": 0.003, "no_work": 0.137})
    assert counter("mxtpu_serve_first_runs_total") == {"prefill": 1,
                                                       "decode": 1}
    # the newest part is polled while unread, at each turn (a: at 4 and 6,
    # then b is the newest), and a part not seen done as its wait opens
    # (a at 6, e at 203: enqueued in the turn that opens its wait)
    assert (la.polls, le.polls) == (3, 1) and lc.polls > 0


def test_exact_follows_the_wait(tel):
    """A part seen done as its wait opens did not block, however long the
    wait: that part is not exact, and neither is the one behind it, whose
    start is then unknown.  A part not seen done blocked the host, however
    short the read: a blocked read after an idle makes the next part exact
    again.  The device running dry inside a phase names that phase."""
    d = _Driver()
    d.at(0, "begin", 1)
    d.at(1, "enter", "decode_dispatch")
    la, a = d.part("decode")
    d.at(2, "enter", "callbacks")
    d.at(3, "commit", 1, work_left=1)
    d.at(4, "begin", 2)
    d.at(5, "enter", "decode_dispatch")
    lb, b = d.part("decode")
    d.at(5.5, "enter", "schedule")                # b enqueued at 5.5
    la.ready = True                               # a: done behind b's enqueue
    d.at(6, "wait", "serve.decode", a)
    d.at(6.05, "enter", "host_sync")              # a: no block
    d.at(7, "enter", "callbacks")
    d.at(8, "commit", 1, work_left=1)
    d.at(9, "begin", 3)
    d.at(10, "enter", "decode_dispatch")
    lc, c = d.part("decode")
    d.at(11, "wait", "serve.decode", b)
    d.at(19, "enter", "host_sync")                # b: blocked, start unknown
    lc.ready = True
    d.at(20, "enter", "callbacks")                # c done inside host_sync
    d.at(21, "commit", 1, work_left=1)
    d.at(22, "begin", 4)
    d.at(23, "enter", "decode_dispatch")
    ld, dd = d.part("decode")
    d.at(25, "wait", "serve.decode", c)           # c: seen done at 20
    d.at(25.05, "enter", "host_sync")
    d.at(26, "enter", "callbacks")
    d.at(27, "commit", 1, work_left=1)
    d.at(28, "begin", 5)
    d.at(29, "wait", "serve.decode", dd)
    d.at(40, "enter", "host_sync")                # d: blocked, after idle
    d.at(41, "commit", 1, work_left=0)
    got = [s[5] for s in telemetry.tracer().spans(prefix="serve.pass")]
    assert [g["exact"] for g in got] == [False, False, False, True]
    assert got[3]["idle_cause"] == "host_sync"
    assert got[3]["idle_before_s"] * 1e3 == pytest.approx(25 - 20)
    assert got[3]["idle_max_s"] * 1e3 == pytest.approx(25 - 19)


def test_a_part_done_as_its_wait_opens_is_not_exact(tel):
    """A part the device finished while the host was busy elsewhere, with
    the next part already behind it (no idle to see): its read does not
    block however long the wait lasts (a stall of the host's own), so it
    is not exact, it ends by the wait's opening, and the part behind it,
    whose start is then unknown, is not exact either."""
    d = _Driver()
    d.at(0, "begin", 1)
    d.at(1, "enter", "decode_dispatch")
    la, a = d.part("decode")
    d.at(2, "enter", "callbacks")
    d.at(3, "commit", 1, work_left=1)
    d.at(4, "begin", 2)
    d.at(5, "enter", "decode_dispatch")
    lb, b = d.part("decode")
    d.at(6, "enter", "callbacks")                 # b queued behind a
    la.ready = True                               # a done, b running
    d.at(16, "wait", "serve.decode", a)
    d.at(19, "enter", "host_sync")                # 3 ms: not a block
    d.at(20, "commit", 1, work_left=1)
    d.at(21, "begin", 3)
    d.at(22, "wait", "serve.decode", b)
    d.at(30, "enter", "host_sync")                # b: blocked
    d.at(31, "commit", 1, work_left=0)
    got = {round(s[3] * 1e3, 6): s for s in
           telemetry.tracer().spans(prefix="serve.pass")}
    assert sorted(got) == [2, 16]
    assert got[2][4] * 1e3 == pytest.approx(16)
    assert [(g[5]["exact"], g[5]["idle_before_s"]) for g in got.values()] \
        == [(False, 0.0), (False, 0.0)]
    assert [(h[0], h[3]) for h in d.heard] == [("decode", False)] * 2


def test_the_timer_follows_telemetry(tel):
    """Parts stamped while telemetry was on are dropped when it goes off
    mid-run (no poll, no record); nothing is timed until it is on again."""
    d = _Driver()
    d.at(0, "begin", 1)
    d.at(1, "enter", "decode_dispatch")
    la, a = d.part("decode")
    d.at(2, "commit", 1, work_left=1)
    telemetry.disable()
    d.at(3, "begin", 2)
    assert not d.sp.tracing and not d.sp._unread and la.polls == 0
    d.at(4, "wait", "serve.decode", None)
    d.at(9, "enter", "host_sync")
    d.at(10, "commit", 1, work_left=1)
    assert d.heard == [] and la.polls == 0


# -- the pass record in the engine ---------------------------------------------
def test_first_run_is_set_once_per_program(tel, model):
    """``first_run`` marks one part per (kind, bucket) an engine runs, the
    first (``PerfAttrib.done``'s dispatch count); the counter agrees."""
    eng = _engine(model)
    try:
        for p in _prompts(5, seed=13):
            assert _run(eng, p, max_new=5).status == "finished"
        spans = telemetry.tracer().spans(prefix="serve.pass")
        keys = {(s[5]["kind"], s[5]["bucket"]) for s in spans}
        firsts = [(s[5]["kind"], s[5]["bucket"]) for s in spans
                  if s[5]["first_run"]]
        assert sorted(firsts) == sorted(keys) and len(spans) > len(keys)
        # the first of each key in time is the one marked
        seen = set()
        for s in spans:
            key = (s[5]["kind"], s[5]["bucket"])
            assert s[5]["first_run"] == (key not in seen)
            seen.add(key)
        fam = telemetry.snapshot()["metrics"]["mxtpu_serve_first_runs_total"]
        assert sum(s["value"] for s in fam["samples"]) == len(keys)
    finally:
        eng.shutdown()


def test_idle_after_an_emptied_engine_is_no_work(tel, model):
    """The engine runs out of work, nothing arrives for 50 ms, then a
    request does: the device's idle before its first part is ``no_work``
    and at least the wait; the idle inside a busy run is named by a host
    phase or the caller's turn, never ``no_work``."""
    eng = _engine(model)
    try:
        _run(eng, _prompts(1, seed=3)[0], max_new=4)
        eng.run()
        assert not eng.has_work()
        time.sleep(0.05)
        t = time.perf_counter()
        _run(eng, _prompts(1, seed=4)[0], max_new=4)
        eng.run()
        spans = telemetry.tracer().spans(prefix="serve.pass")
        after = [s for s in spans if s[3] >= t]
        assert after[0][5]["idle_cause"] == "no_work"
        assert after[0][5]["idle_before_s"] >= 0.05
        assert after[0][5]["reason"] == "idle_start"
        causes = {s[5]["idle_cause"] for s in after[1:]}
        assert "no_work" not in causes
        assert causes <= {None, "caller", "device_wait"} | set(
            profiling.PHASES)
    finally:
        eng.shutdown()


def test_telemetry_off_reads_no_more_clock_and_polls_nothing(model,
                                                             monkeypatch):
    """Off, the timer does nothing: no record, no poll.  On, it reads the
    clock no more than off does: the stamps are the phases' own reads."""
    reads = {"n": 0}

    def clock():
        reads["n"] += 1
        return reads["n"] * 1e-3

    def serve():
        eng = _engine(model)
        eng._sprof._clock = clock
        reads["n"] = 0
        for p in _prompts(3, seed=21):
            eng.submit(p, max_new_tokens=6)
        eng.run()
        n, steps = reads["n"], eng._sprof.summary()["steps"]
        eng.shutdown()
        return n, steps

    def never(*a, **kw):
        raise AssertionError("the pass timer ran with telemetry off")

    assert not telemetry.enabled()
    with monkeypatch.context() as m:
        m.setattr(profiling._PassRecord, "__init__", never)
        m.setattr(profiling.StepProfiler, "_poll", never)
        m.setattr(profiling.StepProfiler, "_stamp", never)
        off = serve()
    telemetry.reset()
    telemetry.enable()
    try:
        on = serve()
        assert telemetry.tracer().spans(prefix="serve.pass")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert on == off


# -- engine integration: three-view agreement ---------------------------------
def test_statusz_metrics_flight_three_views_agree(model, tel,
                                                  monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    eng = _engine(model)
    try:
        for p in _prompts(6, seed=11):
            r = _run(eng, p)
            assert r.status == "finished"
        sz = eng.statusz()["step_profile"]
        assert sz["enabled"] and sz["steps"] > 0
        # view 1 vs view 2: statusz totals == the metrics histogram
        snap = telemetry.registry().snapshot()
        fam = snap["mxtpu_step_phase_seconds"]
        by_phase = {s["labels"]["phase"]: s for s in fam["samples"]}
        for phase, total in sz["totals_s"].items():
            if total == 0.0 and phase not in by_phase:
                continue          # a phase that never ran observes nothing
            assert by_phase[phase]["sum"] == pytest.approx(total)
        # "callbacks" is swept on every commit -> count == steps
        assert by_phase["callbacks"]["count"] == sz["steps"]
        assert sum(sz["totals_s"].values()) == pytest.approx(
            sz["wall_s"])
        # view 3: the flight dump embeds the same ring tail via the
        # statusz snapshot
        path = telemetry.flight.dump_now("profiling_three_view")
        payload = json.loads(open(path).read())
        sections = [v for v in payload["statusz"].values()
                    if isinstance(v, dict) and "step_profile" in v]
        assert sections, list(payload["statusz"])
        emb = sections[0]["step_profile"]
        assert emb["steps"] >= sz["steps"]
        assert emb["recent"], "flight dump carries no ring entries"
        last = emb["recent"][-1]
        assert sum(last["phases"].values()) == pytest.approx(
            last["wall_s"])
    finally:
        eng.shutdown()


def test_disabled_recorder_is_inert_and_tokens_identical(model,
                                                         monkeypatch):
    p = _prompts(1, seed=5)[0]
    monkeypatch.setenv(profiling.ENV_ENABLE, "0")
    off = _engine(model)
    try:
        assert off._sprof is profiling.NOOP_STEP_PROFILER
        assert off.statusz()["step_profile"] == {"enabled": False}
        toks_off = _run(off, p, max_new=6).tokens
    finally:
        off.shutdown()
    monkeypatch.delenv(profiling.ENV_ENABLE)
    on = _engine(model)
    try:
        assert on._sprof.enabled      # default ON
        toks_on = _run(on, p, max_new=6).tokens
        assert on.statusz()["step_profile"]["steps"] > 0
    finally:
        on.shutdown()
    assert toks_on == toks_off


# -- profiler.py concurrency guard --------------------------------------------
def test_profiler_double_start_raises_profiler_active(tmp_path):
    profiler_mod.start(str(tmp_path / "a"))
    try:
        assert profiler_mod.active_logdir() == str(tmp_path / "a")
        with pytest.raises(profiler_mod.ProfilerActive):
            profiler_mod.start(str(tmp_path / "b"))
        # ProfilerActive subclasses RuntimeError (old callers' except
        # clauses keep working) but is distinguishable for the 409 map
        assert issubclass(profiler_mod.ProfilerActive, RuntimeError)
    finally:
        profiler_mod.stop()
    assert profiler_mod.active_logdir() is None
    # released: a fresh window starts fine
    profiler_mod.start(str(tmp_path / "c"))
    profiler_mod.stop()


# -- POST /profilez ------------------------------------------------------------
def test_profilez_capture_conflict_and_rate_limit(model, fleet_cleanup,
                                                  monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_PROFILEZ_DIR", str(tmp_path / "caps"))
    monkeypatch.setenv("MXTPU_PROFILEZ_INTERVAL_S", "30")
    rep = ReplicaServer(_engine(model)).start()
    fleet_cleanup.append(rep)
    st, cap = _post(rep.url, "/profilez",
                    {"duration_s": 0.4, "reason": "unit"})
    assert st == 200, cap
    assert cap["state"] == "running" and cap["replica"] == rep.replica_id
    assert cap["started_epoch"] > 0
    # concurrent window -> clean 409, never a RuntimeError→500
    st2, body2 = _post(rep.url, "/profilez", {"duration_s": 0.2})
    assert st2 == 409 and body2["error"] == "capture_in_progress"
    assert body2["id"] == cap["id"]
    # serving continues during the window
    gst, gen = _post(rep.url, "/generate",
                     {"prompt": [1, 2, 3, 4], "max_new_tokens": 4})
    assert gst == 200 and gen["tokens"]
    meta = _wait_capture(rep.url, cap["id"])
    assert meta["state"] == "done", meta
    assert meta["trace_file"] and os.path.exists(meta["trace_file"])
    # the raw artifact serves back over the id
    with urllib.request.urlopen(
            f"{rep.url}/profilez/{cap['id']}/trace", timeout=10) as resp:
        blob = resp.read()
        assert resp.headers["Content-Type"] == "application/gzip"
    assert blob[:2] == b"\x1f\x8b" and len(blob) > 100
    # back-to-back window -> rate limited with a retry hint
    st3, body3 = _post(rep.url, "/profilez", {"duration_s": 0.2})
    assert st3 == 429 and body3["error"] == "rate_limited"
    assert 0 < body3["retry_after_s"] <= 30
    # bad duration -> 400, unknown id -> 404
    assert _post(rep.url, "/profilez", {"duration_s": -1})[0] == 400
    assert _post(rep.url, "/profilez", {"duration_s": "x"})[0] == 400
    try:
        _get(rep.url, "/profilez/nope")
        assert False, "unknown capture id answered 200"
    except urllib.error.HTTPError as e:
        assert e.code == 404
        assert json.loads(e.read())["error"] == "unknown_capture"


def test_profilez_duration_clamp_and_stop_during_capture(
        model, fleet_cleanup, monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_PROFILEZ_DIR", str(tmp_path / "caps"))
    monkeypatch.setenv("MXTPU_PROFILEZ_MAX_S", "8")
    monkeypatch.setenv("MXTPU_PROFILEZ_INTERVAL_S", "0")
    rep = ReplicaServer(_engine(model)).start()
    st, cap = _post(rep.url, "/profilez", {"duration_s": 9999})
    assert st == 200 and cap["duration_s"] == 8.0   # clamped
    # stopping the replica mid-window ends the capture cleanly (early
    # out on the stop event) and releases the process-global profiler
    t0 = time.monotonic()
    rep.stop()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and profiler_mod.active_logdir() is not None:
        time.sleep(0.05)
    assert profiler_mod.active_logdir() is None
    assert time.monotonic() - t0 < 8.0, \
        "stop waited out the full capture window"
    # the entry leaves "running" (kept artifact or clean fail); the
    # finisher flips state just after releasing the profiler, so poll
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and rep._captures[cap["id"]]["state"] == "running":
        time.sleep(0.05)
    assert rep._captures[cap["id"]]["state"] in ("done", "failed")


def test_capture_fleet_concurrent_windows_and_annotation(
        model, fleet_cleanup, monkeypatch, tmp_path):
    """``capture_fleet`` opens one window per replica concurrently.
    In-process replicas share ONE process-global jax profiler, so
    exactly one window wins and the others refuse cleanly (409 ->
    None) — the annotation records who accepted."""
    monkeypatch.setenv("MXTPU_PROFILEZ_DIR", str(tmp_path / "caps"))
    monkeypatch.setenv("MXTPU_PROFILEZ_INTERVAL_S", "0")
    reps = [ReplicaServer(_engine(model), replica_id=f"cf-{i}").start()
            for i in range(2)]
    for r in reps:
        fleet_cleanup.append(r)
    col = FleetCollector(urls=[r.url for r in reps], interval_s=0)
    fleet_cleanup.append(col)
    col.scrape()                    # views need names before filtering
    results = col.capture_fleet(duration_s=0.3, reason="unit_fleet")
    assert set(results) == {"cf-0", "cf-1"}
    accepted = [n for n, p in results.items() if p]
    assert len(accepted) == 1, results
    ann = [a for a in col.annotations() if a["kind"] == "fleet_capture"]
    assert ann and ann[-1]["reason"] == "unit_fleet"
    caps = {c["replica"]: c for c in ann[-1]["captures"]}
    assert caps[accepted[0]]["accepted"] is True
    assert sum(c["accepted"] for c in caps.values()) == 1
    # role filter: no replica advertises "prefill" here -> no targets
    assert col.capture_fleet(duration_s=0.2, roles=("prefill",)) == {}
    # the winning window still finishes
    winner = [r for r in reps if r.replica_id == accepted[0]][0]
    meta = _wait_capture(winner.url, results[accepted[0]]["id"])
    assert meta["state"] in ("done", "failed")


# -- SLO fast-burn -> automatic capture + flight dump -------------------------
def test_slo_burn_triggers_capture_and_dump_carries_id(
        model, fleet_cleanup, monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("MXTPU_PROFILEZ_DIR", str(tmp_path / "caps"))
    monkeypatch.setenv("MXTPU_PROFILEZ_INTERVAL_S", "0")
    monkeypatch.setenv("MXTPU_PROFILEZ_BURN_S", "0.3")
    col = FleetCollector(urls=[], interval_s=0, port=0)
    fleet_cleanup.append(col)
    col.start()
    monkeypatch.setenv("MXTPU_TRACE_PUSH_URL", col.url + "/trace")
    slow = ReplicaServer(
        _engine(model), replica_id="slow-profilee",
        fault_injector=FaultInjector(
            ";".join(f"delay@{k}:0.4" for k in range(1, 9))))
    fleet_cleanup.append(slow.start())
    col.add_replica(slow.url)
    router = Router([slow.url], scrape_interval_s=0, retries=4,
                    backoff_s=0.01, backoff_max_s=0.05)
    fleet_cleanup.append(router)
    router.scrape()
    ev = SLOEvaluator(parse_slo_spec("total_p90_ms=150"), col,
                      fast_s=120.0, slow_s=240.0, fast_burn=2.0,
                      slow_burn=1.0, min_requests=5,
                      dump_interval_s=0.0)
    assert ev.capture_on_burn and ev.capture_s == 0.3
    col.slo = ev
    for i, p in enumerate(_prompts(8, seed=29)):
        res = router.generate(p.tolist(), max_new_tokens=4,
                              request_id=f"burn-{i}")
        assert res.tokens
        col.scrape()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and len(col.trace_records()) < 10:
        time.sleep(0.05)
    col.scrape()
    assert ev.statusz()["objectives"][0]["firing"], ev.statusz()
    # the alert captured the offender and chained the id into the dump
    dump_ann = [a for a in col.annotations()
                if a["kind"] == "slo_flight_dump"]
    assert dump_ann, col.annotations()
    # dump_interval_s=0 re-dumps every evaluation: later entries
    # legitimately degrade (409 while the first window runs, per-
    # reason dump rate limit) — the FIRST firing carries the real
    # capture id and dump path
    entry = dump_ann[0]["dumps"][0]
    assert entry["replica"] == "slow-profilee"
    assert entry["path"], entry
    cap_id = entry["capture_id"]
    assert cap_id, entry
    meta = _wait_capture(slow.url, cap_id)
    assert meta["state"] in ("done", "failed")
    assert meta["reason"].startswith("slo_burn_total_p90_ms")
    # the on-disk flight dump carries the same capture id
    dumps = list((tmp_path / "flight").glob("flight-*slo_burn*.json"))
    assert dumps
    payload = json.loads(dumps[0].read_text())
    assert payload["extra"]["capture_id"] == cap_id


# -- the stitched fleet timeline ----------------------------------------------
def test_timeline_report_stitches_fleet_run(model, fleet_cleanup,
                                            monkeypatch, tmp_path):
    import timeline_report

    trace_file = tmp_path / "trace.jsonl"
    monkeypatch.setenv("MXTPU_REQUEST_TRACE", str(trace_file))
    rep = ReplicaServer(_engine(model)).start()
    fleet_cleanup.append(rep)
    router = Router([rep.url], scrape_interval_s=0)
    fleet_cleanup.append(router)
    router.scrape()
    for i in range(4):
        res = router.generate([1, 2, 3, 4, 5], max_new_tokens=4,
                              request_id=f"tl-{i}")
        assert res.tokens
    # both line kinds must have flushed (router + engine per request)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        lines = [json.loads(ln) for ln
                 in trace_file.read_text().splitlines()] \
            if trace_file.exists() else []
        if len(lines) >= 8:
            break
        time.sleep(0.05)
    assert len(lines) >= 8, len(lines)
    statusz_file = tmp_path / "statusz.json"
    statusz_file.write_text(json.dumps(
        _get(rep.url, "/statusz.json")))
    out = tmp_path / "TIMELINE.json"
    summary_file = tmp_path / "summary.json"
    rc = timeline_report.main([
        "--trace", str(trace_file), "--statusz", str(statusz_file),
        "--out", str(out), "--json", str(summary_file), "--check"])
    assert rc == 0
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    assert evs and all("name" in e and "ph" in e for e in evs)
    assert all(e.get("dur", 0) >= 0 for e in evs if e["ph"] == "X")
    summary = json.loads(summary_file.read_text())["summary"]
    assert summary["requests"] == 4
    assert summary["router_hops"] == 4
    assert summary["unresolved_hops"] == []
    assert summary["steps"] > 0
    # fleet lines carry clock anchors: nothing floats unanchored
    assert summary["unanchored"] == 0
    # request events land under both the router and the replica pids
    req_pids = {e["pid"] for e in evs if e.get("cat") == "request"}
    assert len(req_pids) == 2
    # and a router-only trace id is what --check must catch
    orphan = tmp_path / "orphan.jsonl"
    orphan.write_text(json.dumps({
        "trace_id": "lost-req", "rid": 1, "status": "finished",
        "source": "router", "replica": "router",
        "events": [{"ev": "pick", "t": 0.0},
                   {"ev": "finished", "t": 0.1}]}) + "\n")
    rc = timeline_report.main([
        "--trace", str(orphan), "--out",
        str(tmp_path / "bad.json"), "--check"])
    assert rc == 1
