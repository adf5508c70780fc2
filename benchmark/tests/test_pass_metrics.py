"""Tests of the readers over the step record's pass timer
(``pass_readers.py`` and the six ``layer_metrics`` files over it): a span
list built by hand, and one the program recorded serving a tiny model on
the CPU.  Arithmetic and selection only, never a device number.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as run_mod       # noqa: E402

MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
TPOT_CELLS = ["mistral7b-l16.chat-steady",
              "granite-4.0-h-micro.chat-concurrent",
              "laguna-s-2.1-ep8-l12.agent-mixed",
              "nemotron-3-super-ep4-l11.reason-long"]
PASS_METRICS = {
    "program.decode_pass_ms_p50": ("program", "tpot_ms_p90", TPOT_CELLS),
    "program.prefill_pass_share.chat": ("program", "tpot_ms_p90",
                                        TPOT_CELLS),
    "engine.device_starved_share.chat": ("engine", "tpot_ms_p90",
                                         TPOT_CELLS),
    "engine.device_starved_share.batch": ("engine", "out_tok_s",
                                          ["mistral7b-l16.doc-batch"]),
    "engine.first_run_ms.chat": ("engine", "tpot_ms_p90", TPOT_CELLS),
    "engine.first_run_ms.batch": ("engine", "out_tok_s",
                                  ["mistral7b-l16.doc-batch"]),
}
PASS_WINDOW = {"start": 100.0, "end": 150.0, "window_s": 50.0,
               "records": []}


def _pass_read(name, ctx):
    mod = run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m")
    return mod.read(ctx)


@pytest.fixture
def pass_tracer():
    """The program's tracer, enabled and empty; off and empty after."""
    import mxnet_tpu as mx

    mx.telemetry.reset()
    mx.telemetry.enable()
    yield mx.telemetry.tracer()
    mx.telemetry.disable()
    mx.telemetry.reset()


def _part(tr, start, dur, kind="decode", bucket=4, exact=True, first=False,
          dispatch=0.002, idle=0.0, cause=None):
    tr.add_complete("serve.pass", start, start + dur, {
        "step": 1, "kind": kind, "bucket": bucket, "rows": 3,
        "reason": None, "exact": exact, "first_run": first,
        "dispatch_s": dispatch, "wait_s": 0.005, "idle_before_s": idle,
        "idle_max_s": idle, "idle_cause": cause}, tid=10_001)


def test_pass_metrics_are_declared_with_their_cells():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (layer, moves, cells) in PASS_METRICS.items():
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "program_span", layer, moves, cells), name
    # the tail versions stay until a benchmark issue retires them
    assert by_name["program.decode_step_ms_p50"]["source"] == "device_trace"


def test_pass_readers_on_a_hand_built_span_list(pass_tracer):
    tr = pass_tracer
    _part(tr, 99.0, 0.5, first=True)                   # before the window
    _part(tr, 100.500, 0.030, first=True, idle=0.3, cause="no_work")
    _part(tr, 100.530, 0.010)
    _part(tr, 100.540, 0.040, kind="prefill", bucket=512, first=True,
          dispatch=0.003)
    _part(tr, 100.583, 0.012, exact=False, idle=0.003, cause="schedule")
    _part(tr, 100.595, 0.011)
    _part(tr, 100.620, 0.020, kind="chunk", bucket=256, first=True,
          idle=0.014, cause="caller")
    _part(tr, 100.640, 0.030, kind="prefill", bucket=512, dispatch=0.003)
    # in flight as the window closes: read by the cell's next look
    _part(tr, 149.990, 20.0, kind="prefill", bucket=512, exact=False)
    ctx = {"window": dict(PASS_WINDOW)}
    # decode parts with exact stamps: 30, 10 and 11 ms
    assert _pass_read("program.decode_pass_ms_p50", ctx) == \
        pytest.approx(11.0)
    # prefill + chunk: 40 + 20 + 30 of 30 + 10 + 40 + 12 + 11 + 20 + 30
    assert _pass_read("program.prefill_pass_share.chat", ctx) == \
        pytest.approx(100.0 * 90 / 153)
    # every cause but no_work, over the 50 s window
    for name in ("engine.device_starved_share.chat",
                 "engine.device_starved_share.batch"):
        assert _pass_read(name, ctx) == pytest.approx(100.0 * 0.017 / 50)
    # decode 4: 32 ms against the median of 12, 14, 13; prefill 512: 43
    # against 33; chunk 256 ran once: left out
    for name in ("engine.first_run_ms.chat", "engine.first_run_ms.batch"):
        assert _pass_read(name, ctx) == pytest.approx(
            (0.032 - 0.013 + 0.043 - 0.033) * 1e3)


def test_a_window_without_first_runs_or_idle_reads_zero(pass_tracer):
    _part(pass_tracer, 101.0, 0.010)
    _part(pass_tracer, 101.010, 0.010, idle=0.5, cause="no_work")
    ctx = {"window": dict(PASS_WINDOW)}
    assert _pass_read("engine.first_run_ms.chat", ctx) == 0.0
    assert _pass_read("engine.device_starved_share.chat", ctx) == 0.0
    assert _pass_read("program.prefill_pass_share.chat", ctx) == 0.0


def test_decode_pass_reads_the_most_run_bucket(pass_tracer):
    """Two decode programs side by side: the reading is the median of the
    one run most, so a shift in the mix that leaves it the most run moves
    nothing; on a tie the larger bucket's."""
    t = 101.0
    for bucket, ms in [(32, 15.0)] * 5 + [(16, 12.0)] * 3 + [(32, 14.0)]:
        _part(pass_tracer, t, ms / 1e3, bucket=bucket)
        t += 0.020
    ctx = {"window": dict(PASS_WINDOW)}
    assert _pass_read("program.decode_pass_ms_p50", ctx) == \
        pytest.approx(15.0)
    for _ in range(2):
        _part(pass_tracer, t, 0.012, bucket=16)
        t += 0.020
    assert _pass_read("program.decode_pass_ms_p50", ctx) == \
        pytest.approx(15.0)
    _part(pass_tracer, t, 0.012, bucket=16)      # 6 against 6: the larger
    assert _pass_read("program.decode_pass_ms_p50", ctx) == \
        pytest.approx(15.0)
    _part(pass_tracer, t + 0.020, 0.012, bucket=16)
    assert _pass_read("program.decode_pass_ms_p50", ctx) == \
        pytest.approx(12.0)


def test_no_exact_decode_part_reads_nothing(pass_tracer):
    _part(pass_tracer, 101.0, 0.010, exact=False)
    _part(pass_tracer, 101.010, 0.030, kind="prefill", bucket=64)
    ctx = {"window": dict(PASS_WINDOW)}
    assert _pass_read("program.decode_pass_ms_p50", ctx) is None
    assert _pass_read("program.prefill_pass_share.chat", ctx) == \
        pytest.approx(75.0)


def test_pass_readers_on_a_span_list_the_program_recorded(pass_tracer,
                                                          monkeypatch):
    """A tiny engine on the CPU records its own ``serve.pass`` spans; the
    six readers read them over a window around the run."""
    import time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import profiling

    # a CPU pass is done before the host looks: here every read counts
    # as blocked, no part is seen done
    class NeverSeenDone:
        def is_ready(self):
            return False

    enqueued = profiling.StepProfiler.enqueued
    monkeypatch.setattr(
        profiling.StepProfiler, "enqueued",
        lambda self, kind, bucket, rows, lead, first_run: enqueued(
            self, kind, bucket, rows, NeverSeenDone(), first_run))
    net = mx.models.gpt(53, 64, num_layers=1, d_model=16, num_heads=2)
    arg_shapes, _, _ = net.infer_shape(data=(1, 64), softmax_label=(1, 64))
    rng = np.random.RandomState(3)
    params = {n: (rng.randn(*s) * 0.3).astype(np.float32)
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    eng = mx.serve.Engine(params, symbol=net, block_size=4, num_blocks=32,
                          max_batch=4, max_model_len=32)
    t0 = time.perf_counter()
    for n in (5, 9, 3):
        eng.submit(rng.randint(0, 53, (n,)).astype(np.int32),
                   max_new_tokens=5)
    eng.run()
    t1 = time.perf_counter()
    eng.shutdown()
    spans = pass_tracer.spans(prefix="serve.pass")
    assert {s[5]["kind"] for s in spans} == {"prefill", "decode"}
    ctx = {"window": {"start": t0, "end": t1, "window_s": t1 - t0,
                      "records": []}}
    values = {name: _pass_read(name, ctx) for name in PASS_METRICS}
    assert all(v is not None and math.isfinite(v) for v in values.values())
    assert values["program.decode_pass_ms_p50"] > 0
    assert 0 < values["program.prefill_pass_share.chat"] < 100
    assert 0 <= values["engine.device_starved_share.chat"] < 100
