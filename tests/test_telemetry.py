"""Unified telemetry layer tests (mxnet_tpu/telemetry).

Covers the registry semantics (labels, histogram buckets, kind/schema
consistency), the Chrome-trace tracer (JSON validity, span nesting,
pid/tid/ts fields), the Prometheus exposition golden output, the
fit-loop / io / serve instrumentation, and the two contracts the rest
of the repo relies on:

  * disabled path: with MXTPU_TELEMETRY unset, every instrumented call
    site resolves the shared no-op objects (near-zero overhead guard)
  * bench records: serve_bench payloads carry the ``telemetry``
    snapshot field
"""

import json
import logging
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import NDArrayIter, PrefetchingIter
from mxnet_tpu.telemetry import Registry


@pytest.fixture
def tel():
    """Enabled telemetry on a clean registry; restores disabled-empty."""
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


# -- registry semantics ------------------------------------------------------
def test_counter_labels_and_increments():
    r = Registry()
    c = r.counter("req_total", "requests", ("route",))
    c.labels(route="/a").inc()
    c.labels(route="/a").inc(3)
    c.labels("/b").inc()
    assert c.labels(route="/a").value == 4
    assert c.labels(route="/b").value == 1
    with pytest.raises(ValueError):
        c.labels(route="/a").inc(-1)          # counters only increase
    with pytest.raises(ValueError):
        c.labels(wrong="x")                   # label-name schema enforced
    with pytest.raises(ValueError):
        c.inc()                               # labeled family needs a child


def test_gauge_set_inc_dec():
    r = Registry()
    g = r.gauge("depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.labels().value == 8


def test_histogram_bucket_semantics():
    r = Registry()
    h = r.histogram("lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    child = h.labels()
    assert child.count == 4
    assert child.sum == pytest.approx(55.55)
    # cumulative le counts, +Inf last
    assert child.cumulative() == [(0.1, 1), (1.0, 2), (10.0, 3),
                                  (float("inf"), 4)]
    # boundary lands in its own bucket (le is inclusive)
    h2 = r.histogram("lat2", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.labels().cumulative()[0] == (1.0, 1)


def test_registry_consistency_enforced():
    r = Registry()
    c = r.counter("x_total", "x", ("a",))
    assert r.counter("x_total", "x", ("a",)) is c      # get-or-create
    with pytest.raises(ValueError):
        r.gauge("x_total")                             # kind mismatch
    with pytest.raises(ValueError):
        r.counter("x_total", label_names=("b",))       # schema mismatch
    h = r.histogram("h_seconds", buckets=(1.0, 5.0))
    assert r.histogram("h_seconds", buckets=(1.0, 5.0)) is h
    with pytest.raises(ValueError):
        r.histogram("h_seconds", buckets=(0.1, 1.0))   # bucket mismatch


# -- disabled path (the overhead-guard contract) -----------------------------
def test_disabled_returns_noop_objects():
    assert not telemetry.enabled()
    telemetry.reset()      # whatever an earlier file of this worker left
    assert telemetry.counter("anything_total") is telemetry.NOOP
    assert telemetry.gauge("anything") is telemetry.NOOP
    assert telemetry.histogram("anything_seconds") is telemetry.NOOP
    assert telemetry.span("anything") is telemetry.NOOP_SPAN
    # chainable and inert
    telemetry.NOOP.labels(a=1).inc()
    telemetry.NOOP.observe(3.0)
    with telemetry.span("x"):
        pass
    assert telemetry.registry().snapshot() == {}


def test_disabled_instrumented_sites_use_noop():
    """With MXTPU_TELEMETRY unset, the iterator, serve-stats and
    fit-loop call sites must all hold the shared no-op objects and the
    registry must stay empty."""
    assert not telemetry.enabled()
    telemetry.reset()      # whatever an earlier file of this worker left
    it = NDArrayIter(np.zeros((8, 3), np.float32),
                     np.zeros(8, np.float32), batch_size=4)
    for _ in it:
        pass
    assert it._tel_batches is telemetry.NOOP

    rec = mx.serve.stats.StatsRecorder()
    assert rec._m_steps is telemetry.NOOP
    assert rec._m_ttft is telemetry.NOOP

    _fit_tiny_mlp(num_epoch=1)
    assert telemetry.registry().snapshot() == {}
    assert telemetry.tracer().trace_events() == [
        {"name": "process_name", "ph": "M",
         "pid": os.getpid(), "args": {"name": "mxtpu host"}}]


def test_disabled_serve_step_allocates_no_span_and_bounds_clock_reads(
        serve_model, monkeypatch):
    """The untraced step's bound: no span object, no annotation, and the
    step instrument reads its clock once per phase interval — begin,
    callbacks and commit (3 a step) plus dispatch, wait and sync (3)
    for each prefill pass and for the decode (the dispatch a call before
    the wait: the loop runs one pass ahead); the engine's own clock
    (request stamps and ``StatsRecorder``) once per schedule(), per
    ``on_step``, per admission, per first token, per decode and per
    finish — the admission stamp is the one read this record added."""
    from mxnet_tpu.telemetry import tracing

    assert not telemetry.enabled()
    net, params = serve_model

    def no_span(*a, **kw):
        raise AssertionError("a span was allocated with telemetry off")

    monkeypatch.setattr(tracing._Span, "__init__", no_span)
    reads = {"prof": 0, "req": 0}

    def prof_clock():
        reads["prof"] += 1
        return float(reads["prof"])

    def req_clock():
        reads["req"] += 1
        return float(reads["req"])

    eng = mx.serve.Engine(params, symbol=net, block_size=4, num_blocks=64,
                          max_batch=4, max_model_len=64, clock=req_clock,
                          max_prefills_per_step=1)
    eng._sprof._clock = prof_clock
    assert eng._sprof.tracing is False
    rng = np.random.RandomState(7)
    for n in (8, 12):
        eng.submit(rng.randint(0, VOCAB, (n,)).astype(np.int32),
                   max_new_tokens=4)
    submits = reads["req"]
    assert submits == 2
    steps = admissions = first_tokens = passes = decodes = 0
    while eng.has_work():
        before = dict(reads)
        eng.step()
        entry = eng._sprof.recent()[-1]
        steps += 1
        passes += entry["prefills"]
        admissions += entry["prefills"]      # whole-prompt: one pass each
        first_tokens += entry["prefills"]
        decodes += bool(entry["decodes"])
        busy = entry["prefills"] + bool(entry["decodes"])
        # a call dispatches the NEXT pass and waits for and syncs the one
        # it reads (the entry's): the same count once the loop is under
        # way, so the bound is on the run, not on each call
        assert reads["prof"] - before["prof"] <= 3 + 3 * (busy + 1) + 1
    # ... and one more read where the loop starts from idle: that call
    # schedules (and dispatches) twice, the pass it reads and the next
    restarts = 1
    assert reads["prof"] == 3 * steps + 3 * (passes + decodes) + restarts
    finishes = 2
    assert reads["req"] - submits \
        == 2 * steps + restarts + admissions + first_tokens + decodes \
        + finishes
    assert telemetry.tracer().spans() == []
    eng.shutdown()


# -- tracer ------------------------------------------------------------------
def test_chrome_trace_json_valid_and_nested(tel, tmp_path):
    with tel.span("outer", step=1):
        with tel.span("inner"):
            pass
    path = tel.tracer().write(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    events = payload["traceEvents"]
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    for e in xs.values():
        assert e["pid"] == os.getpid()
        assert isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # spans nest: inner inside outer's [ts, ts+dur]
    outer, inner = xs["outer"], xs["inner"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert xs["outer"]["args"] == {"step": 1}
    # Perfetto track metadata present
    metas = {e["name"] for e in events if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= metas


def test_traced_decorator(tel):
    @telemetry.traced("work")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    names = [e["name"] for e in tel.tracer().trace_events()
             if e["ph"] == "X"]
    assert names == ["work"]


def test_tracer_event_cap(tel):
    tr = telemetry.SpanTracer(max_events=2)
    for i in range(4):
        tr.add_complete("e", 0.0, 1.0)
    assert len([e for e in tr.trace_events() if e["ph"] == "X"]) == 2
    assert tr.dropped == 2


# -- exporters ---------------------------------------------------------------
def test_prometheus_exposition_golden():
    r = Registry()
    r.counter("req_total", "requests served", ("route",)).labels(
        route="/a").inc(4)
    r.gauge("depth").set(6)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert telemetry.to_prometheus_text(r) == (
        "# TYPE depth gauge\n"
        "depth 6\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n"
        "# HELP req_total requests served\n"
        "# TYPE req_total counter\n"
        'req_total{route="/a"} 4\n')


def test_prometheus_label_escape_roundtrip():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import metrics_report

    r = Registry()
    nasty = 'dir\\name "q"\nline2'
    r.counter("esc_total", "", ("path",)).labels(path=nasty).inc()
    parsed = metrics_report.parse_prometheus_text(
        telemetry.to_prometheus_text(r))
    assert parsed["esc_total"]["samples"][0]["labels"]["path"] == nasty


def test_dump_and_http_endpoint(tel, tmp_path):
    tel.counter("x_total", "x").inc()
    with tel.span("s"):
        pass
    paths = tel.dump(str(tmp_path / "out"))
    assert "x_total 1" in open(paths["prometheus"]).read()
    line = json.loads(open(paths["jsonl"]).read())
    assert line["metrics"]["x_total"]["samples"][0]["value"] == 1
    json.load(open(paths["trace"]))          # valid JSON

    import urllib.request

    server = tel.serve_http(tel.registry(), 0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "x_total 1" in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert js["x_total"]["samples"][0]["value"] == 1
    finally:
        server.shutdown()


# -- instrumented hot paths --------------------------------------------------
def _fit_tiny_mlp(num_epoch=1, batches=4, batch_size=16):
    rng = np.random.RandomState(0)
    X = rng.randn(batches * batch_size, 10).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=batch_size)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=2)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    # pin the CLASSIC loop: these tests contract the per-phase
    # instrumentation of the unfused path (the fused single-dispatch
    # path has its own phase contract in tests/test_fused_step.py)
    os.environ["MXTPU_FUSED_STEP"] = "0"
    try:
        mod.fit(it, num_epoch=num_epoch, kvstore=None)
    finally:
        os.environ.pop("MXTPU_FUSED_STEP", None)
    return batches * num_epoch


def test_fit_loop_phase_metrics(tel):
    n = _fit_tiny_mlp(num_epoch=2)
    snap = tel.registry().snapshot()
    assert snap["mxtpu_fit_batches_total"]["samples"][0]["value"] == n
    assert snap["mxtpu_fit_epochs_total"]["samples"][0]["value"] == 2
    assert snap["mxtpu_fit_epoch_seconds"]["samples"][0]["count"] == 2
    phases = {s["labels"]["phase"]: s["count"]
              for s in snap["mxtpu_fit_phase_seconds"]["samples"]}
    assert phases == {"data_wait": n, "forward_backward": n,
                      "update": n, "update_metric": n}
    # the iterator-side counter agrees with the loop-side one
    assert snap["mxtpu_io_batches_total"]["samples"][0]["value"] == n
    # host spans for every phase + the enclosing epoch span
    names = {e["name"] for e in tel.tracer().trace_events()
             if e["ph"] == "X"}
    assert {"fit.data_wait", "fit.forward_backward", "fit.update",
            "fit.update_metric", "fit.epoch"} <= names
    # jax.monitoring bridge: compiling the step program left compile
    # events in the registry
    assert snap["mxtpu_jax_events_total"]["samples"]


def test_prefetching_iter_wait_metric(tel):
    X = np.arange(32, dtype=np.float32).reshape(8, 4)
    base = NDArrayIter(X, np.zeros(8, np.float32), batch_size=4)
    pf = PrefetchingIter(base)
    n = sum(1 for _ in pf)
    assert n == 2
    snap = tel.registry().snapshot()
    wait = [s for s in snap["mxtpu_io_wait_seconds"]["samples"]
            if s["labels"]["iterator"] == "PrefetchingIter"]
    assert wait and wait[0]["count"] >= n
    produced = {s["labels"]["iterator"]: s["value"]
                for s in snap["mxtpu_io_batches_total"]["samples"]}
    assert produced["PrefetchingIter"] == n


# -- serve bridge ------------------------------------------------------------
VOCAB = 53


@pytest.fixture(scope="module")
def serve_model():
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


def test_serve_engine_registry_bridge(tel, serve_model):
    net, params = serve_model
    eng = mx.serve.Engine(params, symbol=net, block_size=4, num_blocks=64,
                          max_batch=4, max_model_len=64,
                          max_prefills_per_step=2)
    rng = np.random.RandomState(7)
    for n in (8, 12, 16):
        eng.submit(rng.randint(0, VOCAB, (n,)).astype(np.int32),
                   max_new_tokens=6)
    eng.run()
    stats = eng.stats()
    snap = tel.registry().snapshot()

    def value(name):
        return snap[name]["samples"][0]["value"]

    # Prometheus counters and the ServeStats snapshot agree
    assert value("mxtpu_serve_steps_total") == stats.steps
    assert value("mxtpu_serve_tokens_generated_total") == \
        stats.tokens_generated
    assert value("mxtpu_serve_completed_total") == stats.completed == 3
    assert value("mxtpu_serve_prompt_tokens_total") == stats.prompt_tokens
    assert snap["mxtpu_serve_ttft_seconds"]["samples"][0]["count"] == 3
    assert value("mxtpu_serve_blocks_total") == stats.blocks_total
    # drained engine: live gauges read empty
    assert value("mxtpu_serve_queue_depth") == 0
    assert value("mxtpu_serve_running") == 0
    names = {e["name"] for e in tel.tracer().trace_events()
             if e["ph"] == "X"}
    assert {"serve.step", "serve.prefill", "serve.decode"} <= names
    eng.shutdown()


# -- monitor / profiler satellites -------------------------------------------
def test_serve_monitor_formats_none_and_rounds(serve_model, caplog):
    net, params = serve_model

    class _FakeEngine:
        def __init__(self, **overrides):
            from mxnet_tpu.serve.stats import ServeStats

            base = dict(steps=5, queue_depth=1, running=2, completed=3,
                        rejected=0, preemptions=0, evictions=0,
                        tokens_generated=10, prompt_tokens=12,
                        blocks_in_use=4, blocks_total=8,
                        block_utilization=0.5, peak_block_utilization=0.5,
                        ttft_ms_mean=None, ttft_ms_max=None,
                        decode_tok_per_sec=None, total_tok_per_sec=None)
            base.update(overrides)
            self._stats = ServeStats(**base)

        def stats(self):
            return self._stats

    logger = logging.getLogger("test_serve_monitor")
    with caplog.at_level(logging.INFO, logger=logger.name):
        mx.monitor.ServeMonitor(_FakeEngine(), interval=1,
                                logger=logger).log_now()
        mx.monitor.ServeMonitor(
            _FakeEngine(ttft_ms_mean=694.8472, decode_tok_per_sec=18.7501),
            interval=1, logger=logger).log_now()
    first, second = caplog.messages[:2]
    # None fields are '-' (grep-stable), floats one decimal
    assert "ttft_ms=- tok/s=-" in first
    assert "ttft_ms=694.8 tok/s=18.8" in second


def test_profiler_double_start_raises(monkeypatch):
    import mxnet_tpu.profiler as profiler

    calls = []
    monkeypatch.setattr(profiler.jax.profiler, "start_trace",
                        lambda d: calls.append(d))
    monkeypatch.setattr(profiler.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(profiler, "_active_logdir", None)
    profiler.start("/tmp/prof-a")
    with pytest.raises(RuntimeError, match="already active"):
        profiler.start("/tmp/prof-b")
    assert calls == ["/tmp/prof-a"]          # second start never reached jax
    profiler.stop()
    profiler.start("/tmp/prof-b")            # fine after stop
    profiler.stop()


def test_profiler_stop_resets_state_on_error(monkeypatch):
    import mxnet_tpu.profiler as profiler

    monkeypatch.setattr(profiler.jax.profiler, "start_trace",
                        lambda d: None)

    def boom():
        raise RuntimeError("collector failed")

    monkeypatch.setattr(profiler.jax.profiler, "stop_trace", boom)
    monkeypatch.setattr(profiler, "_active_logdir", None)
    profiler.start("/tmp/prof-x")
    with pytest.raises(RuntimeError, match="collector failed"):
        profiler.stop()
    # a failed capture must not wedge the next start
    assert profiler._active_logdir is None
    with pytest.raises(RuntimeError, match="collector failed"):
        with profiler.trace("/tmp/prof-y"):
            pass
    assert profiler._active_logdir is None


# -- tools -------------------------------------------------------------------
def test_metrics_report_renders_all_artifact_forms(tel, tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import metrics_report

    tel.counter("req_total", "requests", ("route",)).labels(route="/a").inc(5)
    h = tel.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    out = str(tmp_path / "out")
    paths = tel.dump(out)
    for target in (out, paths["prometheus"], paths["jsonl"]):
        assert metrics_report.main([target]) == 0
        text = capsys.readouterr().out
        assert "req_total" in text and "route=/a" in text
        assert "lat_seconds" in text and "p99" in text
    # filter narrows the table
    metrics_report.main([out, "--filter", "lat"])
    text = capsys.readouterr().out
    assert "req_total" not in text and "lat_seconds" in text


def test_serve_bench_payload_carries_telemetry_field(tmp_path, monkeypatch):
    """serve_bench's --json artifact always has the telemetry snapshot
    field — tiny in-process run."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench

    out = tmp_path / "serve.json"
    argv = ["serve_bench.py", "--layers", "1", "--d-model", "32",
            "--heads", "2", "--vocab", "67", "--requests", "3",
            "--concurrency", "2", "--prompt-lens", "6,10",
            "--max-new", "3", "--no-serial", "--warmup", "0",
            "--json", str(out)]
    monkeypatch.setattr(sys, "argv", argv)
    serve_bench.main()
    payload = json.loads(open(out).read())
    assert payload["complete"] is True
    assert payload["telemetry"] == {"enabled": False, "metrics": {}}


def test_telemetry_env_gate_subprocess(tmp_path):
    """MXTPU_TELEMETRY=1 end to end in a fresh process: instrumented
    fit leaves the Prometheus file, the JSONL log and a loadable
    Chrome trace in MXTPU_TELEMETRY_DIR at exit."""
    import subprocess

    code = """
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import NDArrayIter

assert telemetry.enabled()
rng = np.random.RandomState(0)
X = rng.randn(32, 10).astype(np.float32)
y = (X.sum(axis=1) > 0).astype(np.float32)
it = NDArrayIter(X, y, batch_size=16)
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, name="fc1", num_hidden=2)
net = mx.sym.SoftmaxOutput(net, name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
mod.fit(it, num_epoch=1, kvstore=None)
"""
    env = dict(os.environ)
    env.update({"MXTPU_TELEMETRY": "1",
                "MXTPU_TELEMETRY_DIR": str(tmp_path / "tel"),
                "JAX_PLATFORMS": "cpu",
                # classic-loop span contract (fit.forward_backward)
                "MXTPU_FUSED_STEP": "0"})
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    prom = open(tmp_path / "tel" / "metrics.prom").read()
    assert "mxtpu_fit_batches_total 2" in prom
    trace = json.load(open(tmp_path / "tel" / "host_trace.json"))
    assert any(e["name"] == "fit.forward_backward"
               for e in trace["traceEvents"])
    line = json.loads(open(tmp_path / "tel" / "metrics.jsonl").read())
    assert line["metrics"]["mxtpu_fit_epochs_total"]["samples"][0]["value"] == 1
