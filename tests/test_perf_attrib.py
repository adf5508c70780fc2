"""Performance-attribution plane (telemetry/perf_attrib.py + serve
wiring) — the PR-17 acceptance surface on CPU:

  * cost table: every serve program family (prefill, chunk, decode,
    draft, draft_chunk, verify, restore) appears in the statusz perf
    section with nonzero flops after warmup — on the fresh-trace path,
    the warm-AOT restart path AND the process-local step-cache-hit
    path (a warm engine must not report an empty perf section)
  * inertness: MXTPU_PERF_ATTRIB and the step record's pass timer
    (telemetry on) in any combination leave greedy tokens byte-identical
    and the AOT fingerprint (_spec_digest) unchanged; telemetry off
    records zero timings
  * three-view agreement: statusz per-program timed counts == the
    mxtpu_serve_program_seconds{kind,bucket} histogram counts in the
    registry == the rows tools/metrics_report.py renders
  * satellites: ServeMonitor perf tail appears only once a run was
    timed (plain lines byte-identical), metrics_report numeric-aware
    label ordering, fleet replica/collector/fleet_report MFU-goodput
    plumbing, tools/perf_report.py breakdown rendering

Device times come from the pass timer (``telemetry/profiling.py``, "The
pass record"); a CPU pass is done before the host looks, so the timed
tests take every read as blocked (``exact``).
"""

import json
import logging
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.telemetry import perf_attrib, profiling

VOCAB = 53
SEQ = 64


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _params(net, seed=3):
    arg_shapes, _, _ = net.infer_shape(data=(1, SEQ),
                                       softmax_label=(1, SEQ))
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


class _NeverSeenDone:
    """A part's output whose readiness the pass timer never sees."""

    def is_ready(self):
        return False


@pytest.fixture
def exact(monkeypatch):
    """Every read counts as blocked: a CPU pass is done before the host
    looks, and here no poll and no look as its wait opens sees it done."""
    enqueued = profiling.StepProfiler.enqueued
    monkeypatch.setattr(
        profiling.StepProfiler, "enqueued",
        lambda self, kind, bucket, rows, lead, first_run: enqueued(
            self, kind, bucket, rows, _NeverSeenDone(), first_run))


@pytest.fixture
def timed(tel, exact):
    """The pass timer on: telemetry enabled, every wait exact."""
    return tel


@pytest.fixture(scope="module")
def model():
    net = mx.models.gpt(VOCAB, SEQ, num_layers=2, d_model=32,
                        num_heads=4)
    return net, _params(net)


@pytest.fixture(scope="module")
def draft_model():
    net = mx.models.gpt(VOCAB, SEQ, num_layers=1, d_model=16,
                        num_heads=2)
    return net, _params(net, seed=5)


def _engine(model, **kw):
    net, params = model
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 32)
    kw.setdefault("max_prefills_per_step", 2)
    return mx.serve.Engine(params, symbol=net, **kw)


def _prompts(n=3, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, (4 + 2 * i,)).astype(np.int32)
            for i in range(n)]


def _serve(eng, prompts, tokens=6):
    reqs = [eng.submit(p, max_new_tokens=tokens) for p in prompts]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    return [tuple(r.tokens) for r in reqs]


ALL_FAMILIES = {"prefill", "chunk", "decode", "draft", "draft_chunk",
                "verify", "restore"}


# -- tentpole: cost table covers every family --------------------------------
def test_cost_table_all_families_nonzero_flops(model, draft_model):
    """Acceptance gate: after warmup every program family this config
    can dispatch appears in the statusz perf cost table with nonzero
    flops — no traffic required (the offline pre-bake default)."""
    dnet, dparams = draft_model
    eng = _engine(model, spec_k=2, draft_params=dparams,
                  draft_symbol=dnet, host_kv_bytes=1 << 24)
    try:
        assert eng.warmup() > 0
        perf = eng.statusz()["perf"]
        assert perf is not None and perf["enabled"]
        rows = perf["programs"]
        assert {r["kind"] for r in rows} == ALL_FAMILIES
        for r in rows:
            assert r["flops"] and r["flops"] > 0, r
            assert r["source"] in ("cost_analysis", "analytic"), r
            # warmup resolves programs without dispatching or timing
            assert r["timed"] == 0 and r["mean_s"] is None, r
        # nothing timed yet -> goodput columns empty, summary timed == 0
        assert perf["timed_steps"] == 0
        assert eng.perf_summary()["timed"] == 0
    finally:
        eng.shutdown()


def test_cost_table_warm_aot_and_cache_hit_paths(model, tmp_path):
    """The cost table fills on ALL THREE resolve paths: fresh trace,
    warm-AOT artifact load after a simulated restart, and a twin
    engine riding the process-local step cache — a warm engine must
    not report an empty perf section."""
    aot_dir = str(tmp_path / "aot")
    prompts = _prompts()

    cold = _engine(model, aot_dir=aot_dir)
    toks = _serve(cold, prompts)
    fresh = {(r["kind"], r["bucket"]): r["flops"]
             for r in cold.statusz()["perf"]["programs"]}
    cold.shutdown()
    assert fresh and all(f and f > 0 for f in fresh.values())

    engine_mod._STEP_CACHE.clear()                # simulated restart
    warm = _engine(model, aot_dir=aot_dir)
    assert _serve(warm, prompts) == toks
    warmed = {(r["kind"], r["bucket"]): r["flops"]
              for r in warm.statusz()["perf"]["programs"]}
    assert set(warmed) == set(fresh)
    for key, f in warmed.items():
        assert f and f > 0, (key, f)

    # twin engine: every program resolves via the step-cache hit path
    twin = _engine(model, aot_dir=aot_dir)
    assert _serve(twin, prompts) == toks
    twinned = {(r["kind"], r["bucket"]) for r in
               twin.statusz()["perf"]["programs"]}
    assert twinned == set(fresh)
    warm.shutdown()
    twin.shutdown()


# -- inertness: knobs never touch tokens or fingerprints ---------------------
def test_pass_timer_and_kill_switch_inert(model, exact, monkeypatch):
    """Greedy tokens and the AOT fingerprint are byte-identical across
    MXTPU_PERF_ATTRIB and the pass timer (telemetry) in any combination
    (the PR 10/11 inertness rule); telemetry off times nothing."""
    prompts = _prompts()

    base = _engine(model)
    toks = _serve(base, prompts)
    digest = base._spec_digest
    perf = base.statusz()["perf"]
    assert perf["timed_steps"] == 0 and perf["tokens"] > 0
    assert all(r["timed"] == 0 for r in perf["programs"])
    base.shutdown()

    telemetry.reset()
    telemetry.enable()
    try:
        on = _engine(model)
        assert _serve(on, prompts) == toks
    finally:
        telemetry.disable()
    assert on._spec_digest == digest
    perf = on.statusz()["perf"]
    assert perf["timed_steps"] > 0 and perf["timed_tokens"] > 0
    assert perf["device_seconds"] > 0
    rows = [r for r in perf["programs"] if r["timed"]]
    assert rows and all(r["mean_s"] > 0 for r in rows)
    # every run of every program was timed, and the shares partition it
    assert all(r["timed"] == r["dispatches"] for r in rows)
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    assert on.perf_summary()["timed"] == sum(r["timed"] for r in rows)
    on.shutdown()
    telemetry.reset()

    monkeypatch.setenv(perf_attrib.ENV_ENABLE, "0")
    off = _engine(model)
    assert _serve(off, prompts) == toks
    assert off._spec_digest == digest
    assert off.statusz()["perf"] is None
    assert off.perf_summary() is None
    off.shutdown()


# -- three-view agreement ----------------------------------------------------
def test_three_view_agreement(model, timed):
    """statusz per-program timed counts == the registry's
    mxtpu_serve_program_seconds{kind,bucket} histogram counts == the
    per-label rows metrics_report renders."""
    import metrics_report

    eng = _engine(model)
    try:
        _serve(eng, _prompts())
        perf = eng.statusz()["perf"]
        by_label = {(r["kind"], str(r["bucket"])): r["timed"]
                    for r in perf["programs"] if r["timed"]}
        assert by_label

        snap = telemetry.snapshot()["metrics"]
        fam = snap["mxtpu_serve_program_seconds"]
        assert fam["kind"] == "histogram"
        hist = {(s["labels"]["kind"], s["labels"]["bucket"]): s["count"]
                for s in fam["samples"]}
        assert hist == by_label

        out = metrics_report.report(snap, "mxtpu_serve_program_seconds")
        rows = [l for l in out.splitlines()
                if l.startswith("mxtpu_serve_program_seconds")]
        assert len(rows) == len(by_label)
        for kind, bucket in by_label:
            assert any(f"bucket={bucket},kind={kind}" in l
                       for l in rows)
    finally:
        eng.shutdown()


def test_metrics_report_numeric_label_order():
    """{kind,bucket} rows render grouped with buckets in numeric order
    (lexical sorting would put 16 before 2)."""
    import metrics_report

    def sample(bucket):
        return {"labels": {"kind": "decode", "bucket": bucket},
                "count": 1, "sum": 0.001, "buckets": [["+Inf", 1]]}

    fake = {"m": {"kind": "histogram", "help": "",
                  "label_names": ["kind", "bucket"],
                  "samples": [sample("16"), sample("2"), sample("4")]}}
    out = metrics_report.report(fake)
    assert (out.index("bucket=2,") < out.index("bucket=4,")
            < out.index("bucket=16,"))


# -- satellite: ServeMonitor perf tail ---------------------------------------
def test_monitor_perf_tail_only_after_a_timed_run(model, exact,
                                                 monkeypatch, caplog):
    logger = logging.getLogger("mxtpu.test.perfmon")
    prompts = _prompts()

    def line_for(eng):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger=logger.name):
            mx.monitor.ServeMonitor(eng, interval=1e9,
                                    logger=logger).log_now()
        return caplog.messages[-1]

    import re

    def normalize(line):
        # wall-clock latency fields honestly differ run to run; the
        # byte-identity contract is about the FORMAT, not the timings
        return re.sub(r"(ttft_ms|tok/s)=[0-9.]+", r"\1=X", line)

    plain = _engine(model)
    _serve(plain, prompts)
    untimed_line = line_for(plain)
    assert "mfu=" not in untimed_line
    plain.shutdown()

    # the kill switch produces the SAME line (byte-identical plain
    # format, not merely "no perf tail")
    monkeypatch.setenv(perf_attrib.ENV_ENABLE, "0")
    killed = _engine(model)
    _serve(killed, prompts)
    assert normalize(line_for(killed)) == normalize(untimed_line)
    killed.shutdown()
    monkeypatch.delenv(perf_attrib.ENV_ENABLE)

    telemetry.reset()
    telemetry.enable()
    try:
        timed = _engine(model)
        _serve(timed, prompts)
    finally:
        telemetry.disable()
        telemetry.reset()
    tail = line_for(timed)
    assert "mfu=" in tail and "tok_flops=" in tail
    timed.shutdown()


# -- satellite: fleet plumbing ----------------------------------------------
def test_replica_state_carries_perf(model, timed):
    from mxnet_tpu.fleet.replica import ReplicaServer

    eng = _engine(model)
    try:
        _serve(eng, _prompts())
        srv = ReplicaServer(eng)
        state = srv._replica_state()
        assert state["perf"]["timed"] > 0
        assert state["perf"]["achieved_tflops"] > 0
    finally:
        eng.shutdown()


def test_collector_role_mfu_goodput_aggregates():
    """Role aggregates: MFU averages over fresh replicas, achieved
    TFLOP/s sums to the role's delivered compute rate; replicas
    without a perf section (older builds, MXTPU_PERF_ATTRIB=0) are
    skipped, not zero-counted."""
    from mxnet_tpu.fleet.collector import FleetCollector

    col = FleetCollector(urls=["http://a:1", "http://b:1",
                               "http://c:1"], interval_s=0)
    try:
        perfs = [{"timed": 5, "mfu": 0.2, "achieved_tflops": 1.0,
                  "tok_flops": 2e6, "cost_per_1k_tokens_s": 0.1},
                 {"timed": 9, "mfu": 0.4, "achieved_tflops": 3.0,
                  "tok_flops": 2e6, "cost_per_1k_tokens_s": 0.3},
                 None]          # a replica predating the perf plane
        for view, perf in zip(col.views(), perfs):
            sec = {"replica": view.url, "role": "decode",
                   "state": "serving", "queue_depth": 0, "running": 0,
                   "stats": {"tokens_generated": 10, "completed": 1,
                             "rejected": 0},
                   "perf": perf}
            view.ring.append(FleetCollector._flatten_replica(sec),
                             now=col.clock())
            view.role = "decode"
            view.last_success_t = col.clock()

        view = col.fleet_view()
        agg = view["roles"]["decode"]
        assert agg["mfu_mean"] == pytest.approx(0.3)
        assert agg["achieved_tflops"] == pytest.approx(4.0)
        rows = {r["url"]: r for r in view["replicas"]}
        assert rows["http://a:1"]["perf_mfu"] == pytest.approx(0.2)
        assert rows["http://b:1"]["perf_timed"] == 9
        assert "perf_mfu" not in rows["http://c:1"]

        import fleet_report

        text = fleet_report.render(view)
        assert "MFU%" in text and "TFLOPS" in text
        role_line = [l for l in text.splitlines()
                     if l.startswith("decode")][0]
        assert "30.0" in role_line and "4.00" in role_line
    finally:
        col.stop()


# -- satellite: tools/perf_report.py ----------------------------------------
def test_perf_report_renders_breakdown(model, timed, tmp_path, capsys):
    import perf_report

    eng = _engine(model)
    try:
        _serve(eng, _prompts())
        doc = {"engine": eng.statusz()}
    finally:
        eng.shutdown()
    path = tmp_path / "statusz.json"
    path.write_text(json.dumps(doc, default=str))

    assert perf_report.main(["--file", str(path)]) == 0
    out = capsys.readouterr().out
    assert "goodput:" in out
    assert "decode" in out and "prefill" in out
    assert "cost_analysis" in out

    # an attribution-off snapshot is a clean nonzero exit, not a crash
    path2 = tmp_path / "empty.json"
    path2.write_text(json.dumps({"engine": {"perf": None}}))
    assert perf_report.main(["--file", str(path2)]) == 1
