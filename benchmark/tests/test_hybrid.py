"""The hybrid cell's own files, on the CPU at the tiny size: the
benchmark's copy of the reference against the program's, the copied
arithmetic against the real parameter tree, the weights' distributions,
the comparison behind ``correct`` on requests a window served (and on
engines that are not the configuration's: ``hybrid_controls``), and the
readers on made-up contexts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid.py -q
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import arith_hybrid       # noqa: E402
import hybrid_cell        # noqa: E402
import hybrid_controls    # noqa: E402
import reference_hybrid   # noqa: E402
import run as run_mod     # noqa: E402
import serve_cell         # noqa: E402

CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".chat-concurrent"
MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
TINY = run_mod.load_json(HERE, "tiny", "configs", CONFIG + ".json")
TINY_MIX = run_mod.load_json(HERE, "tiny", "traffic", "chat-concurrent.json")
FULL = run_mod.load_json(BENCH, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def tiny_model():
    dec = hybrid_cell.describe(TINY)
    return dec, hybrid_cell.make_params(dec, "float32", 2500000001)


def test_the_benchmarks_reference_equals_the_programs(tiny_model):
    """Two independent writings of the same equations on seeded weights:
    float32 both, the recurrence token by token in both, so they agree to
    rounding (1e-4 of logits of order 1)."""
    dec, params = tiny_model
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 27)
    mine = np.asarray(reference_hybrid.logits(
        TINY, params, toks, np.arange(27), vocab_slice=40))
    theirs = np.asarray(dec.reference_logits(params, toks))
    np.testing.assert_allclose(mine, theirs, atol=1e-4)
    assert 0.3 < mine.std() < 3.0


def test_padding_the_reference_changes_nothing(tiny_model):
    """Neither the regrets nor what the sequence carries: the recurrence
    stands still on padding."""
    _, params = tiny_model
    rng = np.random.default_rng(1)
    prompt, gen = rng.integers(0, 96, 9), rng.integers(0, 96, 5)
    one = reference_hybrid.teacher_force(TINY, params, prompt, gen)
    two = reference_hybrid.teacher_force(TINY, params, prompt, gen,
                                         pad_to=32, rows=8)
    assert len(one["regrets"]) == 5 and min(one["regrets"]) >= 0
    assert one["logit_std"] > 0 and one["states"].shape == (6, 4, 16, 16)
    np.testing.assert_allclose(one["regrets"], two["regrets"], atol=1e-5)
    np.testing.assert_allclose(one["states"], two["states"], atol=1e-6)


def test_the_weights_distributions_are_the_configurations():
    """``make_params`` is what the limits of ``correct`` were read on: the
    spreads the configuration file's ``assumed`` block states."""
    cfg = dict(TINY, hidden_size=64, vocab_size=512, mamba_n_heads=8,
               shared_intermediate_size=96)
    dec = hybrid_cell.describe(cfg)
    P = {k: np.asarray(v, np.float64) for k, v in
         hybrid_cell.make_params(dec, "float32", 2 ** 31 + 5).items()}
    assert set(P) == set(dec.param_shapes())
    n = dec.name
    std = lambda k: P[k].std()
    assert std(f"{n}_tok_embed_weight") == pytest.approx(
        dec.logits_scaling / 8.0, rel=0.03)          # sqrt(64) = 8
    assert std(f"{n}_l0_in_proj_weight") * 8 == pytest.approx(1, rel=0.03)
    assert std(f"{n}_l0_ff_out_weight") * 96 ** 0.5 == pytest.approx(
        1, rel=0.05)
    qkv = P[f"{n}_l2_qkv_weight"]
    n_qk = (dec.num_heads + dec.kv_heads) * dec.head_dim
    assert qkv[:n_qk].std() * 8 == pytest.approx(hybrid_cell.QK_GAIN,
                                                 rel=0.05)
    assert qkv[n_qk:].std() * 8 == pytest.approx(1, rel=0.08)
    assert set(np.unique(P[f"{n}_ln_f_gamma"])) == {-1.0, 1.0}
    assert (P[f"{n}_l0_ln1_gamma"] == 1).all() and (P[f"{n}_l0_D"] == 1).all()
    A = np.exp(np.concatenate([P[f"{n}_l{i}_A_log"] for i in (0, 1, 3)]))
    assert 1 <= A.min() and A.max() <= 16 and A.max() - A.min() > 8
    bias = np.concatenate([P[f"{n}_l{i}_dt_bias"] for i in (0, 1, 3)])
    dt = np.log1p(np.exp(bias))
    assert 1e-3 <= dt.min() * 1.001 and dt.max() <= 0.1001
    assert std(f"{n}_l0_conv_weight") == pytest.approx(0.5, rel=0.1)
    other = hybrid_cell.make_params(dec, "float32", 7)
    assert not np.allclose(P[f"{n}_l0_D"] * 0 + np.asarray(
        other[f"{n}_l0_in_proj_weight"]).std(), 0)


@pytest.mark.parametrize("cfg", [TINY, FULL], ids=["tiny", "published"])
def test_parameter_count_equals_the_real_tree(cfg):
    """``arith_hybrid.param_count`` against the shapes the program really
    builds (no array is made at the published size)."""
    dec = hybrid_cell.describe(cfg)
    d = hybrid_cell.dims(cfg)
    assert arith_hybrid.param_count(d) == dec.num_params()
    mats = sum(int(np.prod(s)) for k, s in dec.param_shapes().items()
               if k.endswith("_weight") and "tok_embed" not in k
               and "conv" not in k)
    assert arith_hybrid.matmul_params(d) == mats


def test_published_sizes_as_the_issue_counts_them():
    d = hybrid_cell.dims(FULL)
    mamba, attn = arith_hybrid.layer_params(d)
    assert (d["n_mamba"], d["n_attn"]) == (36, 4)
    assert [i for i, t in enumerate(FULL["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert round(mamba / 1e6, 2) == 76.18 and round(attn / 1e6, 2) == 60.82
    assert round(arith_hybrid.param_count(d) / 1e9, 2) == 3.19
    # one request's state: 36 x (2 MiB of float32 state + 3 bf16 rows)
    assert arith_hybrid.state_bytes(d) == 36 * (2097152 + 26112)
    # a decode step at 45 rows and 18 k tokens of context: weights once,
    # states in and out, the four attention layers' K/V
    need = arith_hybrid.decode_step_bytes(d, 45, 18000)
    assert need == (2 * arith_hybrid.param_count(d)
                    + 90 * arith_hybrid.state_bytes(d) + 18000 * 8192)
    assert 0.5 < arith_hybrid.ssm_update_bytes(d, 45) / need < 0.55
    # a decode token: 2 operations per matrix parameter and the head
    assert 6.3e9 < arith_hybrid.token_flops(d, 400) < 6.5e9


def test_window_flops_adds_decodes_and_prefills():
    d = hybrid_cell.dims(TINY)
    steps = [(0.0, 3, 30, [(7, 7)], 0, 3, 0), (1.0, 0, 0, [(4, 20)], 0, 0, 0)]
    want = (sum(arith_hybrid.token_flops(d, c) for c in (10, 10, 10))
            + arith_hybrid.prefill_flops(d, 7, 7)
            + arith_hybrid.prefill_flops(d, 4, 20))
    assert arith_hybrid.window_flops(d, steps) == want
    one = arith_hybrid.prefill_flops(d, 1, 5)
    assert one == arith_hybrid.token_flops(d, 5)


def _served(params, dec, shapes, tokens_each, hook=None):
    """An engine with one request per (prompt, new) of ``shapes`` stepped
    until each has ``tokens_each`` tokens: (engine, the driver's records)."""
    geo = TINY["engine"]
    eng = hybrid_cell.engine(TINY, dec, params)
    if hook:
        hook(eng)
    rng = np.random.default_rng(11)
    recs = [serve_cell.Rec(eng.submit(
        rng.integers(0, TINY["vocab_size"], p, dtype=np.int32),
        max_new_tokens=g), 0.0, 0.0) for p, g in shapes]
    while min(len(r.req.tokens) for r in recs) < tokens_each:
        eng.step()
    assert geo["prefill_chunk"] < max(p for p, _ in shapes)
    return eng, recs


SPEC = {"finished": 0, "live": 3, "min_tokens": 2, "max_new": 16,
        "max_len": 64}


def test_the_check_compares_running_requests_states(tiny_model):
    """Requests still decoding in their slots: the engine's states are the
    reference's after the same tokens (float32 both: to rounding), for a
    prompt carried over chunk passes too; and a pool rounded to bfloat16
    after every step is seen by the states, not by the tokens."""
    dec, params = tiny_model
    shapes = [(9, 14), (30, 14), (5, 14)]
    verdicts = []
    for hook in (None, hybrid_controls.round_state_after_every_step):
        eng, recs = _served(params, dec, shapes, 8, hook)
        assert all(not r.req.done for r in recs)
        states = hybrid_cell.slot_states(eng, recs)
        eng.shutdown()
        verdicts.append(hybrid_cell.check(params, TINY, SPEC, [], recs,
                                          states))
    good, rounded = verdicts
    assert good["ok"] and good["live"] == 3 and good["tokens"] >= 24
    assert good["max_regret"] <= 1e-4
    assert good["state_err"] < 1e-5 and good["state_f32_share"] > 0.99
    assert not rounded["ok"] and rounded["state_f32_share"] == 0
    assert 1e-3 < rounded["state_err"] < hybrid_cell.STATE_ERR_TOL
    assert rounded["max_regret"] < 0.05


def test_the_check_wants_its_sample():
    verdict = hybrid_cell.check(None, TINY, SPEC, [], [], [])
    assert not verdict["ok"] and "fewer" in verdict["why"]


def test_the_sample_is_of_the_window(tiny_model):
    """Finished inside the window, or running at its end with enough
    tokens; the longest prompt and the longest answer are always in."""
    class Req:
        def __init__(self, p, n):
            self.prompt, self.tokens, self.status = [0] * p, [0] * n, "running"

    def rec(p, n, finish, due, failed=False):
        r = serve_cell.Rec(Req(p, n), due, due)
        r.finish_t, r.failed = finish, failed
        return r

    recs = [rec(10, 5, 1.5, 0.1), rec(40, 3, 1.6, 0.2), rec(8, 16, 1.9, 0.3),
            rec(9, 4, 0.5, 0.0),                   # finished before the window
            rec(9, 4, 1.2, 0.4, failed=True), rec(60, 6, 1.3, 0.5),  # too long
            rec(7, 1, 1.4, 0.6),                   # too few tokens
            rec(12, 6, 1.7, 0.7), rec(11, 7, 1.8, 0.8),
            rec(20, 9, None, 0.9), rec(6, 1, None, 1.0), rec(5, 2, None, 1.1),
            rec(5, 9, None, 1.2)]
    recs[-1].req.status = "waiting"                # preempted: no slot
    out = {"all": recs, "start": 1.0, "end": 2.0}
    spec = dict(SPEC, finished=3, live=2)
    done, live = hybrid_cell.sample(out, spec, 5)
    assert len(done) == 3 and recs[1] in done and recs[2] in done
    assert all(1.0 <= r.finish_t <= 2.0 and not r.failed for r in done)
    assert live == [recs[9], recs[11]]
    again, _ = hybrid_cell.sample(out, spec, 5)
    assert [id(r) for r in again] == [id(r) for r in done]
    # a window that finished too few: running requests make up the number
    done, live = hybrid_cell.sample(out, dict(spec, finished=6, live=0), 5)
    assert len(done) == 5 and live == [recs[9]]


@pytest.mark.parametrize("variant,correct", [("sound", True),
                                             ("no_residual", False)])
def test_a_control_runs_the_cells_own_comparison(variant, correct):
    """``hybrid_controls``: the cell's window and comparison on an engine
    built on other weights than the reference is fed."""
    res = hybrid_controls.run_variant(MANIFEST, CELL, TINY, TINY_MIX,
                                      2 ** 31 + 9, 1.0, variant)
    assert res["correct"] is correct and res["failed"] == 0
    assert hybrid_cell.build.__module__ == "hybrid_cell"


def test_the_cell_refuses_another_state_dtype():
    with pytest.raises(RuntimeError, match="the configuration states"):
        run_mod.run_cell(MANIFEST, CELL, dict(TINY, state_dtype="bfloat16"),
                         TINY_MIX, 3, 1.0, 0)


def test_the_faults_enter_as_data(tiny_model):
    dec, params = tiny_model
    n_q = dec.num_heads * dec.head_dim
    eight = hybrid_controls.faulty_params(dec, params, "scale_8x")
    changed = [k for k in params if eight[k] is not params[k]]
    assert changed == [f"{dec.name}_l{i}_qkv_weight" for i in (2, 6)]
    w0, w1 = np.asarray(params[changed[0]]), np.asarray(eight[changed[0]])
    np.testing.assert_array_equal(w1[:n_q], 8 * w0[:n_q])
    np.testing.assert_array_equal(w1[n_q:], w0[n_q:])
    assert hybrid_controls.faulty_params(dec, params, "sound") == params


def _read(name, ctx):
    return run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m").read(ctx)


def test_hybrid_readers_on_a_made_up_context():
    d = hybrid_cell.dims(FULL)
    peaks = (197e12, 819e9)
    steps = [(1.0, 40, 16000, [], 0, 40, 0), (2.0, 50, 20000, [], 0, 50, 0)]
    need = arith_hybrid.ssm_update_bytes(d, 90)
    ops = [("jit_decode", "ssm_state_update", need / 819e9, True),
           ("jit_decode", "ssm_state_update", need / 819e9, True),
           ("jit_decode", "paged_attention_packed", 1.0, True),
           ("jit_chunk", "ssm_state_update", 9.0, True)]
    total = sum(arith_hybrid.decode_step_bytes(d, s[1], s[2]) for s in steps)
    ctx = {"hybrid": d, "peaks": peaks, "steps": steps,
           "trace_span": (0.5, 2.5),
           "trace": {"ops": ops, "devices": 1,
                     "modules": {"jit_decode": [total / 819e9] * 4}},
           "window": {"start": 0.0, "end": 10.0, "window_s": 10.0}}
    assert _read("kernel.ssm_update_roofline", ctx) == pytest.approx(50.0)
    kv = arith_hybrid.paged_kv_bytes(d, 36000)
    assert kv == 36000 * 8192
    assert _read("kernel.paged_packed_roofline", ctx) == pytest.approx(
        100 * kv / 819e9)
    assert _read("program.decode_mbu.concurrent", ctx) == pytest.approx(25.0)
    mfu = _read("program.mfu.concurrent", ctx)
    assert mfu == pytest.approx(
        100 * arith_hybrid.window_flops(d, steps) / (10.0 * 197e12))
    assert 0 < mfu < 100
    # a program without the kernel or the counter: nothing, no error
    ctx["trace"]["ops"] = ops[2:3]
    assert _read("kernel.ssm_update_roofline", ctx) is None
    ctx["trace"]["ops"] = ops[:2]
    assert _read("kernel.paged_packed_roofline", ctx) is None
    ctx["trace"]["ops"] = ops[2:3]
    assert _read("state.slot_util_mean", ctx) is None
    for name in ("kernel.ssm_update_roofline", "program.decode_mbu.concurrent",
                 "program.mfu.concurrent", "state.slot_util_mean",
                 "kernel.paged_packed_roofline"):
        assert _read(name, {"hybrid": d}) is None
