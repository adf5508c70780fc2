"""Tests of the per-layer readers that read the program's own spans
(``span_readers.py`` and the ``layer_metrics`` files over it), each on a
hand-built span list, on the CPU: arithmetic and selection only.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as run_mod       # noqa: E402
import span_readers         # noqa: E402

MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
# every metric that says it reads the program's spans is held to the
# contract of the cases under "nothing to read", a later PR's too
SPAN_METRICS = [m["name"] for m in MANIFEST["per_layer"]
                if m["source"] == "program_span"]
# the twelve that PR 26 added over the step record
STEP_RECORD_METRICS = [
    "engine.gap_ms_p99.chat", "engine.gap_ms_p99.batch",
    "engine.decode_ms_p50.chat", "engine.dispatch_ms_p50.chat",
    "engine.schedule_ms_p90.batch", "sched.prefill_step_share.chat",
    "sched.queue_wait_ms_p90", "sched.prefill_ms_p90",
    "program.pad_share.chat", "program.pad_share.batch",
    "engine.warmup_s", "engine.retrace_s"]
WINDOW = {"start": 100.0, "end": 150.0, "records": []}


def _read(name, ctx):
    mod = run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m")
    return mod.read(ctx)


@pytest.fixture
def tracer():
    """The program's tracer, enabled and empty; off and empty after."""
    import mxnet_tpu as mx

    mx.telemetry.reset()
    mx.telemetry.enable()
    yield mx.telemetry.tracer()
    mx.telemetry.disable()
    mx.telemetry.reset()


def _step(tr, t, dur, work_left=1, prefills=(), decode=None, schedule=0.001,
          **args):
    """One ``serve.step`` at ``t`` with its children, as the engine
    nests them: ``prefills`` is [(tokens, bucket, dispatch seconds)],
    ``decode`` (dispatch seconds, wait seconds)."""
    step = tr.span("serve.step", work_left=work_left, **args).start(t)
    tr.span("serve.schedule").start(t).finish(t + schedule)
    at = t + schedule
    for tokens, bucket, disp in prefills:
        p = tr.span("serve.prefill", rid=0, kind="prefill", tokens=tokens,
                    bucket=bucket, cached=0).start(at)
        tr.span("serve.prefill_dispatch").start(at).finish(at + disp)
        tr.span("serve.device_wait").start(at + disp).finish(at + disp + .01)
        at += disp + 0.01
        p.finish(at)
    if decode is not None:
        d = tr.span("serve.decode", batch=2, bucket=2).start(at)
        tr.span("serve.decode_dispatch").start(at).finish(at + decode[0])
        tr.span("serve.device_wait").start(at + decode[0]).finish(
            at + decode[0] + decode[1])
        at += decode[0] + decode[1]
        d.finish(at)
    step.finish(t + dur)


def _rec(rid, due, late=0.0, failed=False):
    req = None if rid is None else types.SimpleNamespace(rid=rid)
    return types.SimpleNamespace(req=req, due=due, late=late, failed=failed)


# -- nothing to read ---------------------------------------------------------------

def test_the_twelve_span_metrics_are_declared():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in STEP_RECORD_METRICS:
        assert by_name[name]["source"] == "program_span", name
    setup = {n for n in STEP_RECORD_METRICS
             if by_name[n]["moves"] == "setup_s"}
    assert setup == {"engine.warmup_s", "engine.retrace_s"}
    assert "gen.late_ms_p90" in by_name      # what the benchmark had stays


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_window_or_telemetry_off_reads_nothing(name):
    import mxnet_tpu as mx

    assert _read(name, {}) is None                   # no window in ctx
    was_on = mx.telemetry.enabled()     # a traced cell run in this process
    mx.telemetry.disable()
    mx.telemetry.tracer().add_complete("serve.step", 101.0, 102.0)
    try:
        assert _read(name, {"window": dict(WINDOW)}) is None    # off
    finally:
        mx.telemetry.reset()
        if was_on:
            mx.telemetry.enable()


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_span_reader_reads_nothing(name, tracer,
                                                        monkeypatch):
    """The parent commit's tracer has no ``spans()``: None, no raise."""
    monkeypatch.delattr(type(tracer), "spans")
    assert _read(name, {"window": dict(WINDOW)}) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_an_enabled_but_empty_tracer_reads_nothing(name, tracer):
    assert _read(name, {"window": dict(WINDOW)}) is None


# -- engine ------------------------------------------------------------------------

def test_gaps_skip_idle_predecessors_and_stay_in_the_window(tracer):
    _step(tracer, 99.0, 0.5)                     # before the window
    _step(tracer, 100.0, 1.0)                    # gap to the next: 2 ms
    _step(tracer, 101.002, 1.0, work_left=0)     # idle after: 3 s not a gap
    _step(tracer, 105.002, 1.0)                  # gap to the next: 10 ms
    _step(tracer, 106.012, 1.0)
    _step(tracer, 151.0, 1.0)                    # after the window
    spans = span_readers.in_window({"window": WINDOW}, "serve.step")
    assert span_readers.step_gaps_ms(spans) == pytest.approx([2.0, 10.0])
    for name in ("engine.gap_ms_p99.chat", "engine.gap_ms_p99.batch"):
        assert _read(name, {"window": WINDOW}) == pytest.approx(
            2.0 + 8.0 * 0.99)


def test_decode_schedule_and_dispatch_times(tracer):
    _step(tracer, 100.0, 0.1, schedule=0.004,
          prefills=[(100, 128, 0.002), (50, 64, 0.003)],
          decode=(0.001, 0.030))
    _step(tracer, 101.0, 0.1, schedule=0.002, decode=(0.003, 0.040))
    _step(tracer, 102.0, 0.1, schedule=0.001)            # nothing to run
    _step(tracer, 103.0, 0.1, schedule=0.008, decode=(0.002, 0.050))
    ctx = {"window": WINDOW}
    assert _read("engine.decode_ms_p50.chat", ctx) == pytest.approx(43.0)
    assert _read("engine.schedule_ms_p90.batch", ctx) == pytest.approx(
        4.0 + 0.7 * 4.0)
    # per step: 2 + 3 + 1 = 6, 3 and 2 ms; the idle step has none
    assert sorted(span_readers.dispatch_ms_per_step(
        span_readers.in_window(ctx))) == pytest.approx([2.0, 3.0, 6.0])
    assert _read("engine.dispatch_ms_p50.chat", ctx) == pytest.approx(3.0)
    # 3 steps decoded, one of them carried prefill passes
    assert _read("sched.prefill_step_share.chat", ctx) == pytest.approx(
        100.0 / 3.0)


def test_pad_share_counts_rows_not_passes(tracer):
    _step(tracer, 100.0, 0.1, prefills=[(1100, 2048, 0.001)])
    _step(tracer, 101.0, 0.1, prefills=[(500, 512, 0.001),
                                        (60, 64, 0.001)])
    _step(tracer, 160.0, 0.1, prefills=[(1, 4096, 0.001)])   # after
    want = 100.0 * (1.0 - (1100 + 500 + 60) / (2048 + 512 + 64))
    for name in ("program.pad_share.chat", "program.pad_share.batch"):
        assert _read(name, {"window": WINDOW}) == pytest.approx(want)


def test_warmup_and_retrace_sum_what_came_before_the_window(tracer):
    def resolve(t, build, compile_, source):
        r = tracer.span("serve.resolve", kind="decode", bucket=1,
                        source=source).start(t)
        tracer.span("serve.resolve.build", source=source).start(t).finish(
            t + build)
        tracer.span("serve.resolve.compile").start(t + build).finish(
            t + build + compile_)
        r.finish(t + build + compile_)

    resolve(10.0, 2.0, 0.5, "trace")
    resolve(20.0, 3.0, 0.25, "trace")
    resolve(30.0, 0.1, 0.2, "artifact")
    resolve(120.0, 9.0, 9.0, "trace")       # inside the window: not set-up
    ctx = {"window": WINDOW}
    assert _read("engine.warmup_s", ctx) == pytest.approx(6.05)
    assert _read("engine.retrace_s", ctx) == pytest.approx(5.0)


# -- scheduler ---------------------------------------------------------------------

def test_request_waits_cover_the_requests_due_in_the_window(tracer):
    def queued(rid, t0, t1, resume=0):
        tracer.add_complete("serve.request.queued", t0, t1,
                            {"rid": rid, "resume": resume})

    def prefill(rid, t0, t1, resume=0):
        tracer.add_complete("serve.request.prefill", t0, t1,
                            {"rid": rid, "resume": resume, "passes": 1,
                             "tokens": 8})

    queued(1, 110.0, 110.1), prefill(1, 110.1, 110.4)     # 100 ms, 300 ms
    queued(2, 120.0, 120.4), prefill(2, 120.4, 120.5)     # 400 ms, 100 ms
    queued(2, 125.0, 129.0, resume=1)       # a resume: neither wait counts
    prefill(2, 129.0, 139.0, resume=1)
    queued(3, 148.0, 149.0)                 # admitted, no token yet: 1 s
    queued(9, 130.0, 139.0), prefill(9, 139.0, 149.0)   # due in the ramp
    records = [_rec(1, 109.9, late=0.1), _rec(2, 119.99, late=0.01),
               _rec(3, 147.5, late=0.5),
               _rec(4, 149.2, late=0.3),     # still queued at the end: 0.5 s
               _rec(None, 149.9),            # never submitted: no wait
               _rec(5, 130.0, failed=True)]  # rejected: counted as failed
    ctx = {"window": dict(WINDOW, records=records)}
    spans = span_readers.in_window(ctx, "serve.request.")
    waits, prefills = span_readers.request_waits_ms(spans, records, 150.0)
    assert sorted(waits) == pytest.approx([100.0, 400.0, 500.0, 1000.0])
    assert sorted(prefills) == pytest.approx([100.0, 300.0, 1000.0])
    assert _read("sched.queue_wait_ms_p90", ctx) == pytest.approx(
        500.0 + 0.7 * 500.0)
    assert _read("sched.prefill_ms_p90", ctx) == pytest.approx(
        300.0 + 0.8 * 700.0)
