"""Tests of the benchmark's own code, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They prove control flow, counts and arithmetic; no number they produce is
a device metric.  (They live under ``benchmark/`` because a benchmark PR
may add files only there; ``tests/test_benchmark.py`` is tier-1's door to
them.)  Every test that walks ``BENCHMARK.json`` finds what it needs by
name, as ``run.py`` does: a configuration and a mix are rehearsed at the
size ``tiny/configs/<config>.json`` and ``tiny/traffic/<mix>.json`` give,
and nothing here counts the manifest's entries or pins their order, so a
later PR adds its cell as files and entries (``test_additions.py``).
"""

import json
import math
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import arith            # noqa: E402
import readers          # noqa: E402
import run as run_mod   # noqa: E402
import serve_cell       # noqa: E402
import trace_reduce     # noqa: E402
import traffic          # noqa: E402

MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
BIG_SEED = 2500000001            # more than 32 signed bits hold


def _tiny_file(directory, name):
    """``tests/tiny/<directory>/<name>.json``: the configuration or the mix
    of that name at the size these tests rehearse it."""
    path = os.path.join(HERE, "tiny", directory, name + ".json")
    assert os.path.exists(path), (
        f"BENCHMARK.json names {name!r}: add benchmark/tests/tiny/"
        f"{directory}/{name}.json, its tiny size for the rehearsal on the CPU")
    return run_mod.load_json(path)


def _tiny(workload):
    row = run_mod.find_cell(MANIFEST, workload)
    return (_tiny_file("configs", row["config"]),
            _tiny_file("traffic", row["traffic"]))


def _scheduled_mixes():
    """{name: (mix, vocabulary of the first cell on it)} over the manifest's
    mixes whose loop is ``open`` or ``closed``: those ``Loop``s hold every
    request's shape before the run starts."""
    files = {c["name"]: c["file"] for c in MANIFEST["configs"]}
    out = {}
    for w in MANIFEST["workloads"]:
        mix = run_mod.load_json(BENCH, "traffic", w["traffic"] + ".json")
        if mix["loop"] in ("open", "closed"):
            out.setdefault(w["traffic"], (mix, run_mod.load_json(
                ROOT, files[w["config"]])["vocab_size"]))
    return out


SCHEDULED = _scheduled_mixes()


# -- every cell runs end to end and prints the contract's line ----------------

_RESULTS = {}


def _result(workload, trace):
    key = (workload, trace)
    if key not in _RESULTS:
        config, mix = _tiny(workload)
        _RESULTS[key] = run_mod.run_cell(MANIFEST, workload, config, mix,
                                         BIG_SEED, 1.0, trace)
    return _RESULTS[key]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_prints_contract_keys(workload, trace):
    if _tiny(workload)[0]["kind"] == "train":
        import jax

        # tier-1 runs these with 8 virtual devices: a tiny train cell then
        # fits too few steps into its second for its loss to have come down
        if len(jax.devices()) > 1:
            pytest.skip("a tiny train cell wants one device")
    res = _result(workload, trace)
    json.loads(json.dumps(res))                      # one JSON object
    assert set(res) - {"breakdown"} == {"correct", "attempted", "failed",
                                        "metrics", "device"}
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"        # and says so
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    allowed = set(run_mod.metric_names(MANIFEST, section, workload))
    assert set(res["metrics"]) <= allowed
    if not trace:                  # every end-to-end metric of the cell
        assert set(res["metrics"]) == allowed
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])


def test_every_configuration_and_mix_has_its_tiny_file():
    for w in CELLS:
        _tiny(w)                     # the message says which file to add


def test_no_tpu_no_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


# -- the fixed schedule ------------------------------------------------------------

def _shapes(loop):
    return sorted(zip(loop.prompt_len.tolist(), loop.output_len.tolist()))


def _ids(loop, k=3):
    return [ids[:8].tolist() for ids, _, _ in (loop.start(0.0),
                                               loop.take(1e9))[1][:k]]


@pytest.mark.parametrize("name", list(SCHEDULED))
def test_same_requests_at_same_instants_every_seed(name):
    mix, vocab = SCHEDULED[name]
    a = traffic.loop(mix, 1, 50, vocab)
    b = traffic.loop(mix, BIG_SEED, 50, vocab)
    assert (a.prompt_len == b.prompt_len).all()      # same shapes, same order
    assert (a.output_len == b.output_len).all()
    assert _ids(a) != _ids(b)                        # new token ids
    assert _ids(traffic.loop(mix, BIG_SEED, 50, vocab)) == _ids(b)
    n = len(a.prompt_len)
    if mix["loop"] == "open":
        assert n == round(mix["rate"] * (mix["ramp_s"] + 50))
        assert (a.due == b.due).all()                # due at the same instants
        gaps = np.diff(a.due, prepend=0)
        np.testing.assert_allclose(              # the exponential's quantiles
            np.sort(gaps), traffic.quantiles(mix["gaps"], n, mix["rate"]))
        assert a.due[-1] == pytest.approx(n / mix["rate"])
        assert a.due[-1] == pytest.approx(mix["ramp_s"] + 50, abs=1.0)
    else:
        assert n == mix["shapes"]
    # the multisets are the mid-quantiles, whatever the order
    for lens, spec in ((a.prompt_len, mix["prompt"]),
                       (a.output_len, mix["output"])):
        assert sorted(lens.tolist()) == sorted(
            int(round(v)) for v in traffic.quantiles(spec, n))
    other = traffic.loop(dict(mix, order_seed=5), 1, 50, vocab)
    assert sorted(other.prompt_len.tolist()) == sorted(a.prompt_len.tolist())
    assert (other.prompt_len != a.prompt_len).any()  # in another order
    assert set(mix) >= {"loop", "order_seed", "who", "why", "check"}
    assert "pair_seed" not in mix and "regret_tol" not in mix["check"]


def test_shapes_respect_the_mix_and_the_model():
    configs = {c["name"]: run_mod.load_json(ROOT, c["file"])
               for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        cfg = configs[w["config"]]
        if "engine" not in cfg or w["traffic"] not in SCHEDULED:
            continue
        mix = SCHEDULED[w["traffic"]][0]
        pl = traffic.loop(mix, 3, 50, cfg["vocab_size"])
        assert (pl.prompt_len + pl.output_len
                <= cfg["engine"]["max_model_len"]).all(), w["name"]
        for lens, spec in ((pl.prompt_len, mix["prompt"]),
                           (pl.output_len, mix["output"])):
            if "min" in spec:
                assert lens.min() >= spec["min"], w["name"]
            if "max" in spec:
                assert lens.max() <= spec["max"], w["name"]
    # the two Mistral cells, as PR 24 sized them
    chat, doc = SCHEDULED["chat-steady"][0], SCHEDULED["doc-batch"][0]
    cfg = configs["mistral7b-l16"]
    pl = traffic.loop(chat, 3, 50, cfg["vocab_size"])
    assert 450 <= statistics.median(pl.prompt_len.tolist()) <= 575
    assert 115 <= statistics.median(pl.output_len.tolist()) <= 140
    assert doc["clients"] == 2 * cfg["engine"]["max_batch"]
    # nothing is preempted: a full batch of the longest requests fits
    tokens = cfg["engine"]["block_size"] * (cfg["engine"]["num_blocks"] - 1)
    for mix in (chat, doc):
        pl = traffic.loop(mix, 3, 50, cfg["vocab_size"])
        worst = sorted((pl.prompt_len + pl.output_len).tolist())[
            -cfg["engine"]["max_batch"]:]
        assert sum(worst) + 16 * cfg["engine"]["max_batch"] <= tokens


@pytest.mark.parametrize("directory,names,entry", [
    ("dists", {"lognormal", "loguniform", "exponential", "cycle"}, "at"),
    ("loops", {"open", "closed"}, "Loop")])
def test_distributions_and_loops_are_files(directory, names, entry):
    have = {f[:-3] for f in os.listdir(os.path.join(BENCH, directory))
            if f.endswith(".py")}
    assert have >= names
    for name in have:                # each file has what ``traffic`` calls
        mod = run_mod.load_module(
            os.path.join(BENCH, directory, name + ".py"), name)
        assert callable(getattr(mod, entry, None)), (directory, name)
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "no-such"}, 4)
    with pytest.raises(ValueError):
        traffic.loop({"loop": "no-such"}, 1, 1, 16)


def test_quantiles():
    q = traffic.quantiles({"dist": "exponential"}, 100, rate=2.0)
    assert sum(q) == pytest.approx(50.0)
    assert q == sorted(q) and q[0] > 0
    q = traffic.quantiles({"dist": "lognormal", "median": 100, "sigma": 1.0,
                           "min": 10, "max": 1000}, 101)
    assert q[50] == pytest.approx(100.0) and min(q) >= 10 and max(q) <= 1000
    q = traffic.quantiles({"dist": "loguniform", "min": 10, "max": 1000}, 3)
    assert q[1] == pytest.approx(100.0)
    assert traffic.quantiles({"dist": "cycle", "values": [1, 2]}, 5) == \
        [1, 2, 1, 2, 1]


def test_programs_for_enumerates_chunks():
    geo = {"max_model_len": 4096, "prefill_chunk": 2048, "max_batch": 16}
    progs = serve_cell.programs_for([100, 2048, 2100], geo)
    assert ("prefill", 128) in progs and ("prefill", 2048) in progs
    assert {b for k, b in progs if k == "decode"} == {1, 2, 4, 8, 16}
    # 2100 = 2048-d, then 52+d for d in 0..15: buckets 2048 and 64 or 128
    assert {b for k, b in progs if k == "chunk"} == {2048, 64, 128}


# -- latencies run from the due instant -------------------------------------------

class _FakeReq:
    def __init__(self, prompt, max_new_tokens):
        self.prompt = np.asarray(prompt)
        self.max_new_tokens = max_new_tokens
        self.tokens, self.cache_len, self.status = [], 0, "waiting"

    @property
    def done(self):
        return self.status == "finished"


class _FakeEngine:
    """One token per request per step; a step takes 0.1 fake seconds."""

    def __init__(self, clock):
        self.clock, self.reqs = clock, []
        self.blocks = self
        self.scheduler = self
        self.queue_depth = 0

    def utilization(self):
        return 0.5

    def submit(self, prompt, max_new_tokens):
        req = _FakeReq(prompt, max_new_tokens)
        self.reqs.append(req)
        return req

    def has_work(self):
        return any(not r.done for r in self.reqs)

    def step(self):
        self.clock.t += 0.1
        for r in self.reqs:
            if not r.done:
                r.cache_len = len(r.prompt) + len(r.tokens)
                r.tokens.append(1)
                if len(r.tokens) >= r.max_new_tokens:
                    r.status = "finished"


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def _open_loop(due, new_tokens, ramp_s=0.0, prompt=5):
    mix = {"loop": "open", "rate": 4.0, "ramp_s": ramp_s, "order_seed": 1,
           "prompt": {"dist": "cycle", "values": [prompt]},
           "output": {"dist": "cycle", "values": [new_tokens]},
           "gaps": {"dist": "exponential"}}
    loop = traffic.loop(mix, 0, len(due) / 4.0 - ramp_s, 256)
    assert len(loop.due) == len(due)
    loop.due = np.asarray(due, float)
    return loop


def test_ttft_and_late_count_from_the_due_instant():
    clock = _FakeClock()
    eng = _FakeEngine(clock)
    loop = _open_loop([0.05, 0.12, 0.31], 3)     # seconds after the start
    out = serve_cell.drive(eng, loop, 2.0, clock=clock, sleep=clock.sleep)
    recs = sorted(out["records"], key=lambda r: r.due)
    assert len(recs) == 3 and not any(r.failed for r in recs)
    t0, end = out["t0"], out["end"]
    # request 0: idle engine sleeps to its due instant: on time, first token
    # one step later
    assert recs[0].late == pytest.approx(0.0, abs=1e-9)
    assert recs[0].ttft(end) == pytest.approx(0.1)
    # request 1 fell due at +0.12 while the step to +0.15 was in flight: it
    # is submitted 0.03 late, and its first token (+0.25) counts from +0.12
    assert recs[1].due == pytest.approx(t0 + 0.12)
    assert recs[1].late == pytest.approx(0.03)
    assert recs[1].ttft(end) == pytest.approx(0.13)
    assert recs[2].late == pytest.approx(0.04)
    for r in recs:                               # 3 tokens, 0.1 s apart
        assert r.tpot(end) == pytest.approx(0.1)
    assert out["tokens"] == 9 and out["window_s"] == pytest.approx(2.0)
    assert out["finished_in_window"] == 3


def test_every_request_due_in_the_window_is_measured():
    """No drain, and no dropping either: a request still running, still
    waiting or not even submitted when the window closes enters with the
    time it has waited so far."""
    clock = _FakeClock()
    eng = _FakeEngine(clock)
    # request 0 falls due before the window and is left out although it
    # finishes inside; the window opens between two steps at 0.5 and closes
    # with the step that ends at 1.5
    loop = _open_loop([0.0, 0.58, 0.98, 1.28, 1.42, 9.0], 8, ramp_s=0.45)
    out = serve_cell.drive(eng, loop, 0.95, clock=clock, sleep=clock.sleep)
    t0, end = out["t0"], out["end"]
    assert out["start"] - t0 == pytest.approx(0.5)
    assert end - t0 == pytest.approx(1.5)
    recs = sorted(out["records"], key=lambda r: r.due)
    assert [round(r.due - t0, 2) for r in recs] == [0.58, 0.98, 1.28, 1.42]
    assert out["finished_in_window"] == 2            # requests 0 and 1
    by_due = {round(r.due - t0, 2): r for r in recs}
    assert by_due[0.58].finish_t is not None
    assert by_due[0.98].finish_t is None and by_due[0.98].seen == 5
    assert by_due[0.98].tpot(end) == pytest.approx(0.1)  # its gaps so far
    assert by_due[1.28].ttft(end) == pytest.approx(0.12)
    # due at 1.42 while the last step was in flight: never submitted, has
    # waited 0.08 s, has no gap to report
    assert by_due[1.42].req is None
    assert by_due[1.42].ttft(end) == pytest.approx(0.08)
    assert by_due[1.42].tpot(end) is None
    ttft, tpot = serve_cell.latencies_ms(out)
    assert len(ttft) == 4 and len(tpot) == 3


def _tails(stall_s):
    """ttft/tpot p90 of a 10 s window at 4 req/s under the cell's rule
    and over finished requests only, with a host stall at 8 s."""
    clock = _FakeClock()
    eng = _FakeEngine(clock)
    step = eng.step

    def stalled():
        step()
        if stall_s and clock.t >= 108.0 and not stalled.done:
            clock.t += stall_s
            stalled.done = True
    stalled.done = False
    eng.step = stalled
    loop = _open_loop(np.arange(48) * 0.25, 12, ramp_s=2.0)
    out = serve_cell.drive(eng, loop, 10.0, clock=clock, sleep=clock.sleep)
    ttft, tpot = serve_cell.latencies_ms(out)
    fin = [r for r in out["all"] if r.finish_t is not None
           and out["start"] <= r.finish_t <= out["end"]]
    p90 = lambda xs: arith.percentile(xs, 90)
    return (p90(ttft), p90(tpot),
            p90([(r.first_t - r.due) * 1e3 for r in fin]),
            p90([(r.last_t - r.first_t) / (r.seen - 1) * 1e3 for r in fin]))


def test_a_late_stall_reads_worse_not_better():
    """PR 24's first rule measured requests that FINISHED in the window: a
    stall late in the window kept the requests it delayed out of the
    sample.  Under the cell's rule they enter with what they waited."""
    ttft0, tpot0, fin_ttft0, fin_tpot0 = _tails(0.0)
    ttft1, tpot1, fin_ttft1, fin_tpot1 = _tails(3.0)
    assert ttft1 > 2 * ttft0 and tpot1 > 1.5 * tpot0
    # a stall just before the end: those it delayed have not finished, so
    # the finished-only tails barely notice
    assert fin_ttft1 < 1.1 * fin_ttft0


def test_closed_loop_sends_the_next_request_when_one_finishes():
    clock = _FakeClock()
    eng = _FakeEngine(clock)
    mix = dict(_tiny_file("traffic", "doc-batch"), clients=2, shapes=4,
               ramp_finished=2, output={"dist": "cycle", "values": [3]})
    loop = traffic.loop(mix, 1, 0.95, 256)
    out = serve_cell.drive(eng, loop, 0.95, clock=clock, sleep=clock.sleep)
    # two clients, three steps a request: the window opens at +0.3 when the
    # first two have finished, closes with the step in flight at +1.25 and
    # sees 2 tokens a step for ten steps
    assert out["start"] - out["t0"] == pytest.approx(0.3)
    assert out["tokens"] == 20 and out["window_s"] == pytest.approx(1.0)
    dues = sorted(round(r.due - out["t0"], 2) for r in out["all"])
    assert dues[:6] == [0.0, 0.0, 0.3, 0.3, 0.6, 0.6]
    assert all(not r.failed for r in out["all"])


def test_sensitivity_windows_at_tiny_size():
    import sensitivity

    config = _tiny_file("configs", "mistral7b-l16")
    net, params, eng = serve_cell.build(config, 1)
    mix = _tiny_file("traffic", "chat-steady")
    lens = traffic.loop(mix, 1, 1.0, 256).prompt_len
    eng.warmup([{"kind": k, "bucket": b} for k, b in
                serve_cell.programs_for(lens, config["engine"])])
    conds = ["base", "scale=1.05", "delay=0.5", "stall=0.3"]
    rows = list(sensitivity.windows(eng, config, mix, conds, 1.0, 1))
    eng.shutdown()
    assert [r["condition"] for r in rows] == conds
    n = round(mix["rate"] * 1.0)
    for r in rows:
        assert abs(r["due_in_window"] - n) <= 3 and r["failed"] == 0
        assert r["ttft_ms_p90"] > 0 and r["finished_only"]["n"] > 0


# -- the trace reduction, on a real v5e trace ------------------------------------

@pytest.fixture(scope="module")
def toy():
    raw = trace_reduce.load(os.path.join(BENCH, "fixtures", "toy.xplane.pb"))
    ops = raw["devices"][0]["ops"]
    window = max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
    return trace_reduce.reduce(raw, window), window


def test_trace_busy_and_idle(toy):
    summary, window = toy
    # worked out by hand from the trace's 52 operation events, which do not
    # overlap: two prefill runs of 16190.0 and 16408.75 ns of operations, six
    # decode runs of 2531.25, 2383.75, 2388.75, 2385.0, 2388.75, 2385.0 ns
    # (chiprun_out/probe/structure.txt lists them)
    by_hand = (16190.0 + 16408.75 + 2531.25 + 2383.75 + 2388.75 + 2385.0
               + 2388.75 + 2385.0) * 1e-9
    assert summary["busy_s"] == pytest.approx(by_hand, rel=1e-9)
    assert window == pytest.approx(0.07573643875 - 0.0506668475)
    idle = 1.0 - summary["busy_s"] / summary["window_s"]
    assert idle == pytest.approx(0.9981228, abs=1e-6)
    assert summary["devices"] == 1


def test_trace_modules(toy):
    summary, _ = toy
    mods = summary["modules"]
    assert sorted(mods) == ["jit_decode", "jit_prefill"]
    np.testing.assert_allclose(mods["jit_prefill"], [16207.5e-9, 16426.25e-9])
    np.testing.assert_allclose(
        sorted(mods["jit_decode"]),
        [2393.75e-9, 2396.25e-9, 2396.25e-9, 2397.5e-9, 2400e-9, 2540e-9])
    ctx = {"trace": summary}
    assert readers.module_ms_p50(ctx, "jit_decode") == pytest.approx(
        2396.875e-6)
    assert readers.prefill_share(ctx) == pytest.approx(
        100 * (16207.5 + 16426.25) / 47061.25)


def test_trace_ops_and_gaps(toy):
    summary, _ = toy
    top = summary["device_ops"]
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert top[0][0] == "jit_prefill:fusion:kOutput bf16[512,1024]"
    assert top[0][1] == pytest.approx(2 * 9827.5e-9)
    assert all(m in ("jit_prefill", "jit_decode") for m, _, _, _ in
               summary["ops"])
    assert not any(k for _, _, _, k in summary["ops"])   # no Pallas kernel
    gaps = summary["idle_gaps"]
    assert gaps[0][0] == "serve.decode"              # named by the host span
    assert gaps[0][1] == pytest.approx(0.0049718425, rel=1e-6)
    assert {n for n, _ in gaps} <= {"serve.decode", "serve.prefill",
                                    "serve.step", "unnamed"}


def test_op_label():
    name = ('%fusion.5 = (bf16[512]{0:T(512)}, bf16[512,512]{0,1}) fusion('
            'bf16[512,1024]{1,0} %x.1), kind=kOutput, calls=%fc.7')
    assert trace_reduce.op_label(name) == "fusion:kOutput bf16[512]"
    name = ('%custom-call.3 = bf16[16,8,4,128]{3,2,1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_label(name) == \
        "custom-call:tpu_custom_call bf16[16,8,4,128]"
    name = ('%slice_bitcast_fusion.27.remat2 = bf16[5001,16,8,128]{3,2,1,0} '
            'fusion(bf16[16,5001,16,8,128] %p), kind=kLoop, calls=%f')
    assert trace_reduce.op_label(name) == \
        "slice_bitcast_fusion:kLoop bf16[5001,16,8,128]"


# -- the readers that need the chip, on made-up contexts --------------------------

def _read(name, ctx):
    mod = run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m")
    return mod.read(ctx)


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_reader_with_nothing_to_read_returns_nothing(name):
    assert _read(name, {}) is None


def test_roofline_and_mfu_arithmetic():
    model = {"num_layers": 16, "d_model": 4096, "num_heads": 32,
             "kv_heads": 8, "d_ff": 14336, "vocab": 32768}
    # 1000 positions x 2 x 8 x 128 x 2 B x 16 layers = 65.536 MB
    assert arith.paged_attention_bytes(model, [400, 600]) == 65536000
    steps = [(1.5, 2, 1000, [(512, 512)], 0.4, 2, 0)]
    summary = {"devices": 1, "busy_s": 0.01,
               "modules": {"jit_prefill": [0.004], "jit_decode": [0.003]},
               "ops": [("jit_decode", "custom-call:tpu_custom_call", 0.0008,
                        True),
                       ("jit_chunk", "custom-call:tpu_custom_call", 0.5, True),
                       ("jit_decode", "fusion", 0.002, False)]}
    ctx = {"trace": summary, "peaks": (197e12, 819e9), "model": model,
           "steps": steps, "trace_span": (1.0, 2.0)}
    assert _read("kernel.paged_attn_roofline", ctx) == pytest.approx(
        100 * (65536000 / 819e9) / 0.0008)
    flops = arith.gpt_prefill_flops(16, 4096, 32, 128, 8, 32768, 512, 14336,
                                    True, logits_positions=1)
    assert _read("kernel.prefill_mfu", ctx) == pytest.approx(
        100 * flops / (0.004 * 197e12))
    ctx["trace_span"] = (2.0, 3.0)                   # no step in the trace
    assert _read("kernel.paged_attn_roofline", ctx) is None
    assert _read("trainer.mfu", {"peaks": (197e12, 819e9),
                                 "fwd_flops_per_image": 8.251e9,
                                 "images_per_s_per_chip": 2800.0}) == \
        pytest.approx(100 * 3 * 8.251e9 * 2800 / 197e12)


def test_percentile_matches_numpy():
    vals = list(np.random.default_rng(0).normal(size=97))
    for q in (50, 90, 99):
        assert arith.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert arith.percentile([1.0] * 95 + [float("inf")] * 5, 90) == 1.0
    assert arith.percentile([1.0] * 80 + [float("inf")] * 20, 90) == \
        float("inf")


# -- the copied arithmetic against its originals -----------------------------------

def test_copied_gpt_flops_match_the_program():
    from mxnet_tpu import flops

    args = (16, 4096, 32, 128, 8, 32768)
    assert arith.gpt_token_flops(*args, 1000, 14336, True) == \
        flops.gpt_token_flops(*args, context=1000, d_ff=14336, swiglu=True)
    assert arith.gpt_prefill_flops(*args, 2048, 14336, True,
                                   logits_positions=1) == \
        flops.gpt_prefill_flops(*args, seq_len=2048, d_ff=14336, swiglu=True,
                                logits_positions=1)


def test_copied_count_flops_resnet50():
    import mxnet_tpu as mx
    from mxnet_tpu import flops

    net = mx.models.resnet(num_classes=1000, num_layers=50,
                           image_shape=(3, 224, 224), layout="NHWC",
                           stem="s2d")
    mine = arith.count_flops(net, data=(1, 112, 112, 12))
    assert mine == flops.count_flops(net, data=(1, 112, 112, 12))
    assert mine / 1e9 == pytest.approx(8.251, abs=0.001)


def test_copied_peaks_row():
    from mxnet_tpu import flops

    assert arith.peaks("TPU v5 lite") == flops._TPU_PEAKS["TPU v5 lite"] \
        == (197e12, 819e9)
    with pytest.raises(ValueError):
        arith.peaks("TPU v9")


# -- BENCHMARK.json against its contract ---------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_manifest_names_units_and_text(section):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}[section]
    names = [e["name"] for e in MANIFEST[section]]
    assert len(names) == len(set(names))
    for e in MANIFEST[section]:
        assert set(e) - {"workloads"} == keys, e["name"]
        assert NAME.match(e["name"])
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"])
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        if section == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.1
        if section == "workloads":
            assert NAME.match(e["config"]) and NAME.match(e["traffic"])
            assert e["chips"] in (1, 4)
        if section == "configs":
            assert all(NAME.match(k) for k in e["reduced"])
            assert len(e["reduced"]) <= 16


def test_every_file_a_cell_names_exists():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    for w in MANIFEST["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith("benchmark/")
        body = run_mod.load_json(ROOT, cfg["file"])
        assert os.path.exists(os.path.join(BENCH, body["kind"] + "_cell.py"))
        assert set(cfg["reduced"]) == set(body["reduced"])
        assert os.path.exists(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        for name in run_mod.metric_names(MANIFEST, "per_layer", w["name"]):
            assert os.path.exists(
                os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert used == set(configs)                      # every config is used


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for w in CELLS:
        mine = set(run_mod.metric_names(MANIFEST, "end_to_end", w))
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in MANIFEST["per_layer"]
                 if "workloads" not in m or w in m["workloads"]]
        assert layer
        for m in layer:
            assert m["moves"] in mine, (w, m["name"])


def test_no_file_under_paths_has_a_forbidden_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
