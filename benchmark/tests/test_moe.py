"""The routed-expert cell's own files, on the CPU at the tiny size: the
benchmark's copy of the reference against the program's, the copied
arithmetic against brute-force counts, the weights' distributions, the
comparison behind ``correct`` on requests a window served (and against
references that are not the configuration's: ``moe_controls``), and the
readers on made-up contexts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_moe.py -q
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

import arith_moe          # noqa: E402
import moe_cell           # noqa: E402
import moe_controls       # noqa: E402
import reference_moe      # noqa: E402
import run as run_mod     # noqa: E402

CONFIG = "laguna-s-2.1-ep8-l12"
CELL = CONFIG + ".agent-mixed"
MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
TINY = run_mod.load_json(HERE, "tiny", "configs", CONFIG + ".json")
TINY_MIX = run_mod.load_json(HERE, "tiny", "traffic", "agent-mixed.json")
FULL = run_mod.load_json(BENCH, "configs", CONFIG + ".json")
FULL_MIX = run_mod.load_json(BENCH, "traffic", "agent-mixed.json")


@pytest.fixture(scope="module")
def tiny_moe():
    dec = moe_cell.describe(TINY)
    return dec, moe_cell.make_params(dec, "float32", 2500000001)


def test_the_moe_reference_equals_the_programs(tiny_moe):
    """Two independent writings of the same equations on seeded weights,
    float32 both: one attends in key blocks under a running softmax and
    runs every held expert on every row, the other builds the whole score
    matrix; they agree to rounding (1e-4 of logits of order 1)."""
    dec, params = tiny_moe
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 72)
    mine = np.asarray(reference_moe.forward(TINY, params, toks,
                                            np.arange(72), block=8))
    theirs = np.asarray(dec.reference_logits(params, toks))
    np.testing.assert_allclose(mine, theirs, atol=1e-4)
    assert 0.3 < mine.std() < 3.0


def test_padding_the_moe_reference_changes_nothing(tiny_moe):
    dec, params = tiny_moe
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, TINY["vocab_size"], 21)
    gen = rng.integers(0, TINY["vocab_size"], 9)
    a = reference_moe.teacher_force(TINY, params, prompt, gen, block=2)
    b = reference_moe.teacher_force(TINY, params, prompt, gen, block=12)
    np.testing.assert_allclose(a["regrets"], b["regrets"], atol=1e-4)
    assert len(a["regrets"]) == 9


def test_the_full_configuration_is_the_sources_but_for_its_cuts():
    """Every number of the catalog's config under the same key, but the
    keys ``reduced`` names; the per-layer lists whole."""
    import json

    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    for line in open(path):
        d = json.loads(line)
        if d["name"] == "Laguna-S-2.1":
            row = d
    reduced = set(FULL["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size",
                       "max_position_embeddings"}
    for k, v in row["config"].items():
        if k in reduced and k != "num_experts":
            assert FULL[k] != v and FULL["published"][k] == v, k
        else:
            assert FULL[k] == v, k
    assert FULL["source"] == row["source_url"]
    assert (FULL["num_experts_held"], FULL["expert_offset"]) == (32, 0)
    d = reference_moe.dims(FULL)
    assert d["kinds"].count("full_attention") == 3 and d["L"] == 12
    assert d["heads"] == (48, 72, 72, 72) * 3 and d["dense"][0]
    assert not any(d["dense"][1:])


def test_copied_arithmetic_against_brute_force_counts(tiny_moe):
    """``arith_moe`` against the real parameter tree and against counts
    made position by position."""
    dec, params = tiny_moe
    d = moe_cell.dims(TINY)
    assert arith_moe.param_count(d) == sum(
        int(np.prod(v.shape)) for v in params.values()) == dec.num_params()
    full = moe_cell.dims(FULL)
    assert arith_moe.param_count(full) == 4325526528     # 4.33 B: 8.65 GB
    # one token at context c: every matrix it meets, 2 operations a
    # parameter; its held picks at the uniform share
    c, held = 29, 5
    mats = 0
    for k, v in params.items():
        if k.endswith("_weight") and "tok_embed" not in k \
                and "_experts_" not in k:
            mats += 2 * int(np.prod(v.shape))
    attn = sum(4 * H * d["head_dim"] * (min(c, d["window"]) if w else c)
               for H, w in zip(d["heads"], d["window_layer"]))
    expert = 2 * 3 * d["d_model"] * d["expert_ff"]
    assert arith_moe.token_flops(d, c, held_picks=held) \
        == mats + attn + held * expert
    assert arith_moe.held_picks_expected(d, 16) == 16 * 3 * 4 / 16
    # a pass over positions [first, end): each at its own context
    for first, end in ((0, 5), (0, 30), (7, 19), (20, 41)):
        brute = sum(arith_moe.token_flops(d, p + 1, head=False)
                    for p in range(first, end)) \
            + 2 * d["vocab"] * d["d_model"]
        assert arith_moe.prefill_flops(d, end - first, end) \
            == pytest.approx(brute, rel=1e-12), (first, end)
    # decode bytes: global layers the whole context, window layers 8
    per = 2 * d["kv_heads"] * d["head_dim"] * 2
    assert arith_moe.paged_kv_bytes(d, 2, 50 + 70) == per * (
        2 * 120 + 6 * 2 * 8)
    assert arith_moe.moe_bytes(d, 3, 10) == 2 * (3 * expert // 2
                                                 + 10 * 2 * d["d_model"])
    steps = [(1.0, 2, 120, [(7, 19)], 0, 2, 0)]
    want = 2 * arith_moe.token_flops(d, 0) \
        + sum(4 * H * d["head_dim"] * (2 * 8 if w else 120)
              for H, w in zip(d["heads"], d["window_layer"])) \
        + arith_moe.prefill_flops(d, 7, 19)
    assert arith_moe.window_flops(d, steps) == pytest.approx(want)


def test_the_moe_weights_distributions_are_pinned(tiny_moe):
    """What the limits of ``correct`` were read on: matrices N(0, 1/fan_in)
    (an expert's fan in is its rows' width), gains 1, query and key rows
    times their kind's gain, the router's rows plain."""
    dec, params = tiny_moe
    assert moe_cell.qk_gain(dec, "sliding_attention") \
        == pytest.approx(2 ** 0.5)
    full = moe_cell.describe(FULL)
    var = 0.5 * 1.4852030263919618 ** 4 + 0.5
    assert moe_cell.qk_gain(full, "full_attention") \
        == pytest.approx((2 / var ** 0.5) ** 0.5)
    big = moe_cell.make_params(
        moe_cell.describe(dict(TINY, hidden_size=256)), "float32", 7)
    for name, w in big.items():
        w = np.asarray(w)
        if name.endswith("gamma"):
            assert (w == 1).all()
            continue
        fan_in = w.shape[-2] if "_experts_" in name else w.shape[-1]
        if name.endswith("qkv_weight"):
            i = int(name.split("_l")[1].split("_")[0])
            n_qk = (dec.heads[i] + dec.kv_heads) * dec.head_dim
            gain = moe_cell.qk_gain(dec, dec.layer_types[i])
            assert w[:n_qk].std() * fan_in ** 0.5 == pytest.approx(gain, rel=0.05)
            w = w[n_qk:]
        assert w.std() * fan_in ** 0.5 == pytest.approx(1.0, rel=0.06), name
    again = moe_cell.make_params(dec, "float32", 2500000001)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in params.items())
    other = moe_cell.make_params(dec, "float32", 2500000002)
    assert not (np.asarray(other["moe_head_weight"])
                == np.asarray(params["moe_head_weight"])).all()


def test_the_sample_takes_the_longest_and_the_shortest_context():
    class Req:
        def __init__(self, p, g, status="finished"):
            self.prompt, self.tokens, self.status = [0] * p, [0] * g, status

    class Rec:
        def __init__(self, req, due, finish_t):
            self.req, self.due, self.finish_t = req, due, finish_t
            self.failed = False

    out = {"start": 10.0, "end": 20.0, "all": [
        Rec(Req(100, 8), 1.0, 12.0), Rec(Req(900, 8), 2.0, 13.0),
        Rec(Req(300, 8), 3.0, 14.0), Rec(Req(400, 8), 4.0, 15.0),
        Rec(Req(500, 1), 5.0, 16.0),                 # too few tokens
        Rec(Req(600, 8), 6.0, 9.0),                  # before the window
        Rec(Req(50, 8, "running"), 7.0, None),
        Rec(Req(700, 8, "running"), 8.0, None),
        Rec(Req(800, 8, "waiting"), 9.0, None)]}
    spec = {"finished": 3, "live": 2, "min_tokens": 2}
    done, live = moe_cell.sample(out, spec, seed=5)
    assert len(done) == 3 and len(live) == 2
    lens = sorted(len(r.req.prompt) for r in done + live)
    assert lens[0] == 50 and lens[-1] == 900 and 500 not in lens
    assert 600 not in lens and 800 not in lens
    assert moe_cell.sample(out, spec, seed=5)[0] == done


def test_each_control_moves_its_own_number_at_tiny_size(tiny_moe):
    """The same sampled requests against the sound reference and each
    faulty one: the sound reading is rounding, every fault's is not."""
    dec, params = tiny_moe
    eng = moe_cell.engine(TINY, dec, params)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, TINY["vocab_size"], n),
                       max_new_tokens=g) for n, g in ((50, 14), (12, 10))]
    eng.run()
    probe = eng.routed_probe()      # what the engine's last passes computed
    eng.shutdown()

    class Rec:
        def __init__(self, req):
            # the request of the last decode pass stands for a running one
            self.req = req
            self.finish_t = None if req.rid in probe["decode_rids"] else 1.0

    recs = sorted((Rec(r) for r in reqs), key=lambda r: r.finish_t is None)
    assert [r.finish_t is None for r in recs] == [False, True]
    spec = dict(TINY_MIX["check"], finished=1, live=1)

    def check(**kw):
        return moe_cell.check(params, TINY, spec, recs[:1], recs[1:], probe,
                              **kw)

    sound = check()
    assert sound["ok"] and sound["mean_regret"] < 1e-5
    assert sorted(sound["shapes"]) == [[12, 10], [50, 14]]
    for fault in ("window_496", "held_norm", "no_yarn"):
        v = check(fault=fault)
        assert v["mean_regret"] > 100 * max(sound["mean_regret"], 1e-7), fault
    # the two precisions below flip no token at this size: they are what
    # ffn_err is for (the routed blocks of the engine's own last decode
    # pass and last span against the reference's on the same rows, every
    # routed layer), and nothing else moves it
    assert sound["ffn_err"] < 1e-5 and sound["pick_flips"] == [0, 7]
    assert sound["pick_flips_by_layer"] == [0] * 7
    for kind in ("decode", "span"):
        assert sound["ffn_err_by_pass"][kind]["rows"] >= 7
    assert sound["per_request"][0][0] == sound["max_regret"] or \
        sound["per_request"][1][0] == sound["max_regret"]
    for fault in ("router_bf16", "acc_bf16", "held_norm"):
        v = check(fault=fault)
        assert v["ffn_err"] > 100 * sound["ffn_err"], fault
        # (the limit sits above bfloat16 ACTIVATIONS' rounding, which this
        # float32 engine has none of: only the chip's readings say that a
        # bfloat16 router or accumulator lies beyond it, PERF.md)
        assert fault != "held_norm" or not v["ok"]
    for fault in ("window_496", "no_yarn"):
        assert check(fault=fault)["ffn_err"] < 1e-5, fault
    # the engine's input to the first routed block against the
    # reference's: rounding as configured; a window one position short or
    # a rotary without the blend in front of it is not
    assert sound["u_err"] < 1e-5 and len(sound["u_err_by_layer"]) == 7
    for fault in ("window_496", "no_yarn"):
        assert check(fault=fault)["u_err"] > 1e-3, fault
    for fault in ("held_norm", "router_bf16", "acc_bf16"):
        assert check(fault=fault)["u_err"] == sound["u_err"], fault
    short = moe_cell.check(params, TINY, dict(spec, long_context=500),
                           recs[:1], recs[1:], probe)
    assert not short["ok"] and "none past" in short["why"]
    gone = moe_cell.check(params, TINY, spec, recs[:1], recs[1:],
                          dict(probe, decode_rids=[]))
    assert not gone["ok"] and "last decode pass" in gone["why"]


def test_the_controls_ride_one_run_of_the_cell():
    """``moe_controls.run_variants``: the cell's own result, then a verdict
    per variant on what that run sampled; ``moe_cell.run`` is put back."""
    res, verdicts = moe_controls.run_variants(
        MANIFEST, CELL, TINY, TINY_MIX, 2 ** 31 + 9, 1.0,
        ["held_norm", "no_yarn"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(verdicts) == {"held_norm", "no_yarn"}
    assert not verdicts["held_norm"]["ok"]
    assert moe_cell.run.__module__ == "moe_cell"
    with pytest.raises(SystemExit):
        moe_controls.run_variants(MANIFEST, CELL, TINY, TINY_MIX, 3, 1.0,
                                  ["nonsense"])


def _read_moe(name, ctx):
    return run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m").read(ctx)


def test_moe_readers_on_a_made_up_context(monkeypatch):
    import span_readers

    d = moe_cell.dims(FULL)
    peaks = (197e12, 819e9)
    steps = [(1.0, 32, 190000, [], 0, 32, 0), (2.0, 32, 190032, [], 0, 32, 0)]
    args = {"moe_picks": 3520, "moe_picks_held": 440, "moe_load_max": 33,
            "moe_experts_hit": 250, "batch": 32}
    spans = [("serve.decode", i, 0, t, t + 0.01, dict(args))
             for i, t in enumerate((0.6, 1.6))]
    spans += [("serve.step", 9, 0, 0.5, 1.0,
               {"blocks_global": 12000, "blocks_window": 1056,
                "window_blocks_freed": 2})]
    monkeypatch.setattr(span_readers, "in_window",
                        lambda ctx, prefix="serve.": [
                            s for s in spans if s[0].startswith(prefix)])
    ops = [("jit_decode", "pallas_call:tpu_custom_call bf16[384,2048]", 0.006,
            True),
           ("jit_decode", "paged_attention:tpu_custom_call bf16[32,48,128]",
            0.004, True),
           ("jit_decode", "fusion:kLoop bf16[32,3072]", 0.001, False),
           ("jit_chunk", "span_attention:tpu_custom_call", 0.5, True)]
    ctx = {"moe": d, "peaks": peaks, "steps": steps, "trace_span": (0.5, 2.5),
           "window": {"window_s": 2.0, "start": 0.0, "end": 3.0},
           "trace": {"ops": ops, "modules": {"jit_decode": [0.016, 0.016]},
                     "devices": 1, "busy_s": 1.0}}
    expert = 3 * 3072 * 1024
    need_s = 2 * (250 * expert * 2 + 440 * 2 * 3072 * 2) / 819e9
    assert _read_moe("kernel.moe_experts_roofline", ctx) \
        == pytest.approx(100 * need_s / 0.006)
    kv = 2 * 8 * 128 * 2 * (3 * 380032 + 9 * 64 * 512)
    assert _read_moe("kernel.paged_attn_roofline.groups", ctx) \
        == pytest.approx(100 * kv / 819e9 / 0.004)
    assert _read_moe("moe.experts_hit_share", ctx) \
        == pytest.approx(100 * 250 / (32 * 11))
    assert _read_moe("moe.load_max_over_mean", ctx) \
        == pytest.approx(66 / (880 / 32))
    assert _read_moe("kv.window_saved_share", ctx) == pytest.approx(
        100 * (1 - (3 * 12000 + 9 * 1056) / (12 * 12000)))
    fixed = (arith_moe.param_count(d) - 11 * 32 * expert
             - d["vocab"] * d["d_model"]) * 2
    need = 2 * fixed + 2 * 250 * expert * 2 + kv
    assert _read_moe("program.decode_mbu.agents", ctx) \
        == pytest.approx(100 * need / 819e9 / 0.032)
    assert _read_moe("program.mfu.agents", ctx) == pytest.approx(
        100 * arith_moe.window_flops(d, steps) / (2.0 * 197e12))
    for name in ("kernel.moe_experts_roofline", "moe.experts_hit_share",
                 "kv.window_saved_share", "program.decode_mbu.agents",
                 "kernel.paged_attn_roofline.groups", "program.mfu.agents",
                 "moe.load_max_over_mean"):
        assert _read_moe(name, {"hybrid": {}, "peaks": peaks}) is None, name


def test_the_experts_time_is_the_conditional_where_one_encloses_them():
    """Span programs compile the layer's two paths into one conditional,
    whose event spans the gather, both products and the sum: that is the
    layer's time there, not the kernels inside it; decode has none, and
    counts its kernels; a conditional of another width is not the
    layer's."""
    reader = run_mod.load_module(os.path.join(
        BENCH, "layer_metrics", "kernel.moe_experts_roofline.py"), "m")
    ops = [("jit_decode", "gmm:tpu_custom_call bf16[384,2048]", 0.5, True),
           ("jit_decode", "gmm:tpu_custom_call bf16[384,3072]", 0.3, True),
           ("jit_decode", "fusion:kLoop bf16[384,3072]", 0.1, False),
           ("jit_chunk", "conditional f32[2048,3072]", 0.19, False),
           ("jit_chunk", "gmm:tpu_custom_call bf16[5120,2048]", 0.09, True),
           ("jit_chunk", "gmm:tpu_custom_call bf16[5120,3072]", 0.05, True),
           ("jit_chunk", "conditional s32[7]", 0.4, False),
           ("jit_prefill(1a2b)", "gmm:tpu_custom_call bf16[2560,2048]", 0.02,
            True),
           ("jit_prefill(1a2b)", "conditional f32[1024,3072]", 0.01, False),
           ("jit_other", "conditional f32[8,3072]", 9.0, False)]
    assert reader.layer_seconds(ops, 3072) \
        == pytest.approx(0.8 + 0.19 + 0.02)


def test_the_mix_is_data_over_what_is_there():
    assert FULL_MIX["loop"] == "closed" and FULL_MIX["clients"] == 40
    assert FULL_MIX["prompt"] == {"dist": "loguniform", "min": 1024,
                                  "max": 16384}
    assert FULL_MIX["output"]["values"] == [256, 384, 512, 640, 768]
    assert FULL["engine"]["max_batch"] == 32 < FULL_MIX["clients"]
    assert 16384 + 768 <= FULL["engine"]["max_model_len"]
