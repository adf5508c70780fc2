"""One step record: the serve engine's phases, counts and request waits
as spans on one clock (mxnet_tpu/telemetry/{tracing,profiling}.py,
serve/{engine,scheduler}.py).

Counts and structure only, never a time: ids and parents, the phase
spans tiling their step (under an injected clock), the args each span
carries, the request spans sharing ``rid``, every phase reaching a
``TraceAnnotation`` before its work, and inertness (greedy tokens
identical with telemetry on and off).  The untraced path's bounds live
beside the overhead guard in tests/test_telemetry.py.
"""

import inspect
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.telemetry import profiling

VOCAB = 53
NAME, ID, PARENT, START, END, ARGS = range(6)
PHASE_SPANS = {"serve." + p for p in profiling.PHASES}


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


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
def model():
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4)
    return net, _rand_params(net, S, seed=3)


def _engine(model, **kw):
    net, params = model
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("prefill_chunk", 0)
    return mx.serve.Engine(params, symbol=net, **kw)


def _prompt(n, seed=7):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(np.int32)


def _spans(prefix="serve."):
    return telemetry.tracer().spans(prefix=prefix)


def _named(spans, name):
    return [s for s in spans if s[NAME] == name]


def _children(spans, parent):
    return [s for s in spans if s[PARENT] == parent[ID]]


# -- the tracer: ids, parents, one reader ------------------------------------------

def test_spans_reader_ids_parents_and_filters(tel):
    tr = tel.tracer()
    with tel.span("outer", n=1) as outer:
        with tel.span("inner.a"):
            pass
        after = tr.add_complete("inner.b", 5.0, 6.0, {"k": 2})
        outer.set(late=3)
    spans = tr.spans()
    by_name = {s[NAME]: s for s in spans}
    assert len({s[ID] for s in spans}) == 3
    assert by_name["outer"][PARENT] is None
    assert by_name["inner.a"][PARENT] == by_name["outer"][ID]
    # a span recorded after the fact is a child of the span open then
    assert by_name["inner.b"][ID] == after
    assert by_name["inner.b"][PARENT] == by_name["outer"][ID]
    assert by_name["inner.b"][START:ARGS] == (pytest.approx(5.0),
                                              pytest.approx(6.0))
    assert by_name["outer"][ARGS] == {"n": 1, "late": 3}
    assert by_name["outer"][START] <= by_name["inner.a"][START]
    assert [s[NAME] for s in tr.spans(prefix="inner.")] \
        == ["inner.b", "inner.a"]                       # by start
    assert [s[NAME] for s in tr.spans(prefix=("outer", "inner.a"),
                                      since=6.5)] == ["outer", "inner.a"]
    assert [s[NAME] for s in tr.spans(until=5.5)] == ["inner.b"]
    assert tr.current() is None                         # nothing left open


def test_span_parents_are_per_thread(tel):
    tr = tel.tracer()
    seen = {}

    def other():
        with tel.span("thread.b"):
            seen["inside"] = tr.current()

    with tel.span("thread.a") as a:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert tr.current() == a.id
    b = _named(tr.spans(), "thread.b")[0]
    assert b[PARENT] is None and seen["inside"] == b[ID]


def test_explicit_times_and_unwound_stack(tel):
    """start()/finish() take the caller's instants; finishing an outer
    span drops what an exception left open above it."""
    tr = tel.tracer()
    outer = tr.span("o").start(10.0)
    tr.span("leaked").start(10.5)           # never finished
    outer.finish(12.0)
    assert tr.current() is None
    (o,) = tr.spans()
    assert (o[NAME], o[START], o[END]) == ("o", pytest.approx(10.0),
                                           pytest.approx(12.0))


# -- the step: structure ------------------------------------------------------------

def test_every_step_span_has_the_right_parent(tel, model):
    eng = _engine(model, max_prefills_per_step=1)
    eng.submit(_prompt(9), max_new_tokens=3)
    eng.submit(_prompt(7), max_new_tokens=3)
    eng.run()
    eng.shutdown()
    spans = _spans()
    by_id = {s[ID]: s for s in spans}
    assert len(by_id) == len(spans)
    steps = _named(spans, "serve.step")
    assert steps and all(s[PARENT] is None for s in steps)
    # the loop runs one pass ahead: a call dispatches the next pass
    # (phases under the step) and then reads the one before, whose span
    # holds its wait and its bookkeeping
    want = {"serve.schedule": {"serve.step"},
            "serve.callbacks": {"serve.step"},
            "serve.prefill": {"serve.step"}, "serve.decode": {"serve.step"},
            "serve.prefill_dispatch": {"serve.step"},
            "serve.decode_dispatch": {"serve.step"},
            "serve.device_wait": {"serve.prefill", "serve.decode"},
            "serve.host_sync": {"serve.prefill", "serve.decode"}}
    seen = set()
    for s in spans:
        if s[NAME] in want:
            assert by_id[s[PARENT]][NAME] in want[s[NAME]], s
            seen.add(s[NAME])
    assert seen == set(want)
    # the second step dispatched the decode of both requests, then read
    # the pass that prefilled one and decoded the other: its children in
    # the order they ran
    assert [c[NAME] for c in _children(spans, steps[1])] \
        == ["serve.schedule", "serve.decode_dispatch", "serve.prefill",
            "serve.decode", "serve.callbacks"]
    assert steps[0][ARGS]["ahead"] == 0 and steps[1][ARGS]["ahead"] == 1
    passes = _named(spans, "serve.prefill") + _named(spans, "serve.decode")
    for p in passes:
        assert [c[NAME] for c in _children(spans, p)] \
            == ["serve.device_wait", "serve.host_sync"]


def test_phase_spans_tile_the_step_and_equal_the_profile(tel, model):
    eng = _engine(model)
    ticks = {"now": 1000.0}

    def clock():                    # every read is 1 ms after the last
        ticks["now"] += 0.001
        return ticks["now"]

    eng._sprof._clock = clock
    eng.submit(_prompt(9), max_new_tokens=3)
    eng.submit(_prompt(14, seed=8), max_new_tokens=2)
    eng.run()
    entries = {e["step"]: e for e in eng._sprof.recent()}
    eng.shutdown()
    spans = _spans()
    steps = _named(spans, "serve.step")
    assert len(steps) == len(entries) >= 3
    for step in steps:
        entry = entries[step[ARGS]["step"]]
        assert step[END] - step[START] == pytest.approx(entry["wall_s"])
        phases = []
        for c in _children(spans, step):
            phases += [c] if c[NAME] in PHASE_SPANS \
                else _children(spans, c)
        assert {p[NAME] for p in phases} <= PHASE_SPANS
        # tiling: each phase starts where the last ended, from the
        # step's start to its end, with no hole and no overlap
        edge = step[START]
        for p in phases:
            assert p[START] == pytest.approx(edge, abs=1e-9)
            edge = p[END]
        assert edge == pytest.approx(step[END], abs=1e-9)
        total = {}
        for p in phases:
            key = p[NAME][len("serve."):]
            total[key] = total.get(key, 0.0) + p[END] - p[START]
        assert total == pytest.approx(entry["phases"])
        assert sum(total.values()) == pytest.approx(entry["wall_s"])


def test_spec_decode_keeps_both_dispatches_in_one_decode(tel, model):
    """Greedy speculative decoding dispatches twice per step (draft,
    then verify, each with its wait) and reads the pass in the call that
    enqueues it: one ``serve.decode`` from the draft's wait on, the
    verify's dispatch inside it, four phase spans that tile it."""
    net, params = model
    draft = {k: v for k, v in params.items() if not k.startswith("gpt_l1_")}
    eng = _engine(model, spec_k=2, draft_params=draft, draft_num_heads=4,
                  draft_window=0)
    eng.submit(_prompt(9), max_new_tokens=6)
    eng.run()
    eng.shutdown()
    spans = _spans()
    decodes = _named(spans, "serve.decode")
    assert decodes
    for d in decodes:
        kids = _children(spans, d)
        assert [k[NAME] for k in kids if k[NAME] in PHASE_SPANS] \
            == ["serve.device_wait",
                "serve.decode_dispatch", "serve.device_wait",
                "serve.host_sync"]
        assert kids[0][START] == pytest.approx(d[START])
        assert kids[-1][END] == pytest.approx(d[END])
    # the draft's ingest is a sub-interval of the first dispatch phase
    by_id = {s[ID]: s for s in spans}
    ingests = _named(spans, "serve.spec_ingest")
    assert ingests and all(
        by_id[i[PARENT]][NAME] == "serve.decode_dispatch" for i in ingests)
    assert all(s[ARGS]["ahead"] == 0 for s in _named(spans, "serve.step"))


# -- the step: counts where the work happens ------------------------------------------

def test_step_args_count_the_work(tel, model):
    eng = _engine(model, max_prefills_per_step=1)
    first_id = eng._step_id + 1
    reqs = [eng.submit(_prompt(n, seed=n), max_new_tokens=4)
            for n in (9, 11, 6)]
    eng.run()
    used = eng.blocks.blocks_in_use
    eng.shutdown()
    steps = _named(_spans(), "serve.step")
    assert [s[ARGS]["step"] for s in steps] \
        == list(range(first_id, first_id + len(steps)))
    for s in steps:
        assert set(s[ARGS]) == {"step", "queue", "running", "blocks_in_use",
                                "emitted", "preemptions", "work_left",
                                "ahead"}
        assert all(type(v) is int for v in s[ARGS].values())
    assert sum(s[ARGS]["emitted"] for s in steps) \
        == sum(len(r.tokens) for r in reqs) == 12
    # one admission per step: the queue after scheduling drains 2, 1, 0
    assert [s[ARGS]["queue"] for s in steps[:3]] == [2, 1, 0]
    assert [s[ARGS]["running"] for s in steps[:3]] == [0, 1, 2]
    assert [s[ARGS]["work_left"] for s in steps] \
        == [1] * (len(steps) - 1) + [0]
    assert steps[-1][ARGS]["blocks_in_use"] == used == 0
    assert max(s[ARGS]["blocks_in_use"] for s in steps) > 0
    assert all(s[ARGS]["preemptions"] == 0 for s in steps)


def test_decode_args_batch_and_bucket(tel, model):
    eng = _engine(model, max_prefills_per_step=4)
    for n in (7, 8, 9):
        eng.submit(_prompt(n, seed=n), max_new_tokens=3)
    eng.run()
    eng.shutdown()
    decodes = _named(_spans(), "serve.decode")
    assert [(d[ARGS]["batch"], d[ARGS]["bucket"]) for d in decodes] \
        == [(3, 4), (3, 4)]


@pytest.mark.parametrize("case", ["whole", "chunked", "prefix_hit"])
def test_prefill_args(tel, model, case):
    if case == "whole":
        eng = _engine(model)
        req = eng.submit(_prompt(11), max_new_tokens=2)
        eng.run()
        want = [("prefill", 11, 16, 0)]
    elif case == "chunked":
        eng = _engine(model, prefill_chunk=8)
        req = eng.submit(_prompt(21), max_new_tokens=2)
        eng.run()
        want = [("chunk", 8, 8, 0), ("chunk", 8, 8, 0), ("chunk", 5, 8, 0)]
    else:
        eng = _engine(model, prefix_cache=True)
        eng.submit(_prompt(19), max_new_tokens=2)
        eng.run()
        telemetry.tracer().clear()
        req = eng.submit(_prompt(19), max_new_tokens=2)
        eng.run()
        # four whole blocks of four are reused; the last span is
        # recomputed through the chunk program
        want = [("chunk", 3, 4, 16)]
    eng.shutdown()
    spans = _spans()
    passes = _named(spans, "serve.prefill")
    assert [(p[ARGS]["kind"], p[ARGS]["tokens"], p[ARGS]["bucket"],
             p[ARGS]["cached"]) for p in passes] == want
    assert all(p[ARGS]["rid"] == req.rid for p in passes)
    assert all(type(v) in (int, str) for p in passes
               for v in p[ARGS].values())
    (whole,) = _named(spans, "serve.request.prefill")
    assert whole[ARGS] == {"rid": req.rid, "resume": 0,
                           "passes": len(want),
                           "tokens": sum(w[1] for w in want)}
    # admission to first token: it starts where the queue wait ends and
    # ends inside the last pass
    (queued,) = _named(spans, "serve.request.queued")
    assert queued[ARGS] == {"rid": req.rid, "resume": 0}
    assert whole[START] == pytest.approx(queued[END]) \
        == pytest.approx(req.admit_t)
    assert whole[END] == pytest.approx(req.first_token_t)
    assert passes[-1][START] <= whole[END] <= passes[-1][END]


# -- requests: spans that share rid ---------------------------------------------------

def test_queued_span_exists_at_admission_and_again_after_preemption(
        tel, model):
    prompts = [_prompt(n, seed=n) for n in (12, 17, 22, 9)]
    eng = _engine(model, num_blocks=20, max_prefills_per_step=2)
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    eng.step()
    # unfinished (no token budget spent yet) and already on record
    admitted = [r for r in reqs if r.admit_t is not None]
    assert admitted and not any(r.done for r in reqs)
    queued = _named(_spans(), "serve.request.queued")
    assert sorted(q[ARGS]["rid"] for q in queued) \
        == sorted(r.rid for r in admitted)
    for q in queued:
        req = next(r for r in reqs if r.rid == q[ARGS]["rid"])
        assert q[START] == pytest.approx(req.submit_t)
        assert q[END] == pytest.approx(req.admit_t)
        # the span that caused it: this step's schedule phase
        by_id = {s[ID]: s for s in _spans()}
        assert by_id[q[PARENT]][NAME] == "serve.schedule"
    eng.run()
    stats = eng.stats()
    eng.shutdown()
    assert stats.preemptions > 0, "no cache pressure: the test is vacuous"
    spans = _spans()
    queued = _named(spans, "serve.request.queued")
    for req in reqs:
        mine = [q for q in queued if q[ARGS]["rid"] == req.rid]
        assert [q[ARGS]["resume"] for q in mine] \
            == [0] + [1] * req.n_preemptions
        # a resume's wait starts at the preemption, not at the submit
        assert all(q[START] > req.submit_t for q in mine[1:])
        done = [p for p in _named(spans, "serve.request.prefill")
                if p[ARGS]["rid"] == req.rid]
        assert [p[ARGS]["resume"] for p in done] \
            == [0] + [1] * req.n_preemptions
    steps = _named(spans, "serve.step")
    assert steps[-1][ARGS]["preemptions"] == stats.preemptions
    assert [s[ARGS]["preemptions"] for s in steps] \
        == sorted(s[ARGS]["preemptions"] for s in steps)    # lifetime count


# -- the device trace sees the phases ----------------------------------------------

def test_every_phase_reaches_trace_annotation_before_its_work(
        tel, model, monkeypatch):
    log = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            log.append(("enter", self.name, self.kwargs))

        def __exit__(self, *exc):
            log.append(("exit", self.name, None))

    monkeypatch.setattr(tel.tracer(), "_ann_cls", Annotation)
    eng = _engine(model)

    def mark(obj, attr, phase):
        real = getattr(obj, attr)

        def wrapped(*a, **kw):
            log.append(("work", phase, None))
            return real(*a, **kw)

        monkeypatch.setattr(obj, attr, wrapped)

    mark(eng.scheduler, "schedule", "serve.schedule")
    mark(eng, "_prefill_fn", "serve.prefill_dispatch")
    mark(eng, "_decode_fn", "serve.decode_dispatch")
    mark(eng, "_unpack_outs", "serve.device_wait")
    mark(eng, "_maybe_finish", "serve.host_sync")
    mark(eng._stats, "on_step", "serve.callbacks")
    step0 = eng._step_id
    eng.submit(_prompt(9), max_new_tokens=3)
    eng.run()
    eng.shutdown()
    open_now, worked = [], set()
    for kind, name, kwargs in log:
        if kind == "enter":
            open_now.append(name)
        elif kind == "exit":
            assert open_now.pop() == name               # strictly nested
        else:
            # the work runs INSIDE the annotation named after its phase
            assert open_now[-1] == name, (name, open_now)
            worked.add(name)
    assert worked == PHASE_SPANS and not open_now
    steps = [kw for kind, name, kw in log
             if kind == "enter" and name == "serve.step"]
    assert steps == [{"step": step0 + i + 1} for i in range(len(steps))]
    decodes = [kw for kind, name, kw in log
               if kind == "enter" and name == "serve.decode"]
    assert decodes and all(
        {"batch": 1, "bucket": 1, "kv_tiles": 1}.items() <= kw.items()
        and set(kw) == {"batch", "bucket", "kv_tiles", "kv_tiles_table"}
        for kw in decodes)


# -- start-up ---------------------------------------------------------------------

def test_resolve_spans_name_the_program_and_its_source(tel, model,
                                                       monkeypatch):
    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    eng = _engine(model)
    assert eng.warmup([{"kind": "decode", "bucket": 2},
                       {"kind": "prefill", "bucket": 16}]) == 2
    spans = _spans()
    (warm,) = _named(spans, "serve.warmup")
    resolves = _named(spans, "serve.resolve")
    assert [(r[ARGS]["kind"], r[ARGS]["bucket"], r[ARGS]["source"])
            for r in resolves] == [("decode", 2, "trace"),
                                   ("prefill", 16, "trace")]
    for r in resolves:
        assert r[PARENT] == warm[ID]
        kids = _children(spans, r)
        assert [k[NAME] for k in kids] == ["serve.resolve.build",
                                           "serve.resolve.compile"]
        assert kids[0][ARGS] == {"source": "trace"}
    # a program the warm-up missed resolves inside the step that needs
    # it, under that step's dispatch phase; a second use resolves nothing
    telemetry.tracer().clear()
    eng.submit(_prompt(5), max_new_tokens=3)
    eng.run()
    eng.shutdown()
    spans = _spans()
    by_id = {s[ID]: s for s in spans}
    late = _named(spans, "serve.resolve")
    assert sorted((r[ARGS]["kind"], r[ARGS]["bucket"]) for r in late) \
        == [("decode", 1), ("prefill", 8)]
    assert sorted(by_id[r[PARENT]][NAME] for r in late) \
        == ["serve.decode_dispatch", "serve.prefill_dispatch"]


def test_aot_artifact_resolves_are_labelled(tel, model, monkeypatch,
                                            tmp_path):
    for source in ("trace", "artifact"):
        monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
        telemetry.tracer().clear()
        eng = _engine(model, aot_dir=str(tmp_path))
        eng.warmup([{"kind": "decode", "bucket": 1}])
        eng.shutdown()
        (r,) = _named(_spans(), "serve.resolve")
        assert r[ARGS]["source"] == source
        (b,) = _named(_spans(), "serve.resolve.build")
        assert b[ARGS] == {"source": source}


# -- one clock, and inertness --------------------------------------------------------

@pytest.mark.parametrize("cls", [mx.serve.Engine, mx.serve.Scheduler,
                                 mx.serve.stats.StatsRecorder])
def test_request_stamps_default_to_the_span_clock(cls):
    default = inspect.signature(cls.__init__).parameters["clock"].default
    assert default is time.perf_counter
    assert inspect.signature(profiling.StepProfiler.__init__) \
        .parameters["clock"].default is time.perf_counter


def test_injected_clock_stamps_requests_and_their_spans(tel, model):
    ticks = {"now": 50.0}

    def clock():
        ticks["now"] += 0.5
        return ticks["now"]

    eng = _engine(model, clock=clock)
    req = eng.submit(_prompt(9), max_new_tokens=2)
    eng.run()
    eng.shutdown()
    assert 50.0 < req.submit_t == req.queued_t < req.admit_t \
        < req.first_token_t < req.finish_t
    (q,) = _named(_spans(), "serve.request.queued")
    (p,) = _named(_spans(), "serve.request.prefill")
    assert (q[START], q[END]) == (pytest.approx(req.submit_t),
                                  pytest.approx(req.admit_t))
    assert (p[START], p[END]) == (pytest.approx(req.admit_t),
                                  pytest.approx(req.first_token_t))


def test_greedy_tokens_identical_with_telemetry_on_and_off(model):
    prompts = [_prompt(n, seed=n) for n in (9, 21, 13)]

    def serve():
        eng = _engine(model, prefill_chunk=8, num_blocks=24)
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        digest = eng._spec_digest
        eng.shutdown()
        return [r.tokens for r in reqs], digest

    assert not telemetry.enabled()
    off = serve()
    telemetry.reset()
    telemetry.enable()
    try:
        on = serve()
        assert _named(_spans(), "serve.step")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert on == off


def test_a_step_that_raises_leaves_no_span_open(tel, model, monkeypatch):
    eng = _engine(model)
    eng.submit(_prompt(9), max_new_tokens=3)
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", "")
    real = eng._unpack_outs

    def boom(*a, **kw):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(eng, "_unpack_outs", boom)
    with pytest.raises(RuntimeError, match="fell over"):
        eng.step()
    monkeypatch.setattr(eng, "_unpack_outs", real)
    eng.step()                       # the next begin() unwinds the wreck
    eng.shutdown()
    assert telemetry.tracer().current() is None
    steps = _named(_spans(), "serve.step")
    assert [s[PARENT] for s in steps] == [None] * len(steps)
