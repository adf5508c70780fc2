"""The one-branch cell's own files, on the CPU at the tiny size: the
benchmark's copy of the reference against the program's, the copied
arithmetic against brute-force counts, the weights' distributions, the
comparison behind ``correct`` on requests an engine served (and against
references that are not the configuration's: ``branch_controls``), and the
readers on made-up contexts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_branch.py -q
"""

import json
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

import arith_branch       # noqa: E402
import arith_hybrid       # noqa: E402
import branch_cell        # noqa: E402
import branch_controls    # noqa: E402
import reference_branch   # noqa: E402
import run as run_mod     # noqa: E402

CONFIG = "nemotron-3-super-ep4-l11"
CELL = CONFIG + ".reason-long"
MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
TINY = run_mod.load_json(HERE, "tiny", "configs", CONFIG + ".json")
TINY_MIX = run_mod.load_json(HERE, "tiny", "traffic", "reason-long.json")
FULL = run_mod.load_json(BENCH, "configs", CONFIG + ".json")
FULL_MIX = run_mod.load_json(BENCH, "traffic", "reason-long.json")


@pytest.fixture(scope="module")
def tiny_branch():
    dec = branch_cell.describe(TINY)
    return dec, branch_cell.make_params(dec, "float32", 3600000001)


def test_the_branch_reference_equals_the_programs(tiny_branch):
    """Two independent writings of the same equations on seeded weights,
    float32 both; they agree to rounding (1e-4 of logits of order 1), and
    so do the states after the last position."""
    dec, params = tiny_branch
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 48)
    mine, states, _ = reference_branch.forward(TINY, params, toks,
                                               np.arange(48), block=8)
    theirs = np.asarray(dec.reference_logits(params, toks))
    np.testing.assert_allclose(np.asarray(mine), theirs, atol=1e-4)
    assert 0.3 < np.asarray(mine).std() < 3.0
    assert states.shape == (2, 4, 16, 16)


def test_padding_the_branch_reference_changes_nothing(tiny_branch):
    """A sequence padded to another length, its rows repeated: the same
    regrets, the same states (the recurrence stands still on padding)."""
    dec, params = tiny_branch
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, TINY["vocab_size"], 21)
    gen = rng.integers(0, TINY["vocab_size"], 9)
    a = reference_branch.teacher_force(TINY, params, prompt, gen, block=2)
    b = reference_branch.teacher_force(TINY, params, prompt, gen, block=8,
                                       pad_to=56, rows=12, taps=True)
    np.testing.assert_allclose(a["regrets"], b["regrets"], atol=1e-4)
    np.testing.assert_allclose(np.asarray(a["states"]),
                               np.asarray(b["states"]), atol=1e-5)
    assert len(a["regrets"]) == 9 and not a["ffn_inputs"]
    assert sorted(b["ffn_inputs"]) == [1, 3, 5, 7]
    assert b["ffn_inputs"][1].shape == (9, 32)


def test_the_full_configuration_is_the_sources_but_for_its_cuts():
    """Every number of the catalog's config under the same key, but the
    keys ``reduced`` names; the cut is one whole period at the model's
    ratio."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = None
    for line in open(path):
        d = json.loads(line)
        if d["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
            row = d
    reduced = set(FULL["reduced"])
    assert reduced == {"num_hidden_layers", "hybrid_override_pattern",
                       "n_routed_experts", "vocab_size",
                       "max_position_embeddings", "num_nextn_predict_layers"}
    for k, v in row["config"].items():
        if k in reduced and k != "n_routed_experts":
            assert FULL[k] != v and FULL["published"][k] == v, k
        else:
            assert FULL[k] == v, k
    assert FULL["source"] == row["source_url"]
    assert (FULL["num_experts_held"], FULL["expert_offset"]) == (128, 0)
    whole = row["config"]["hybrid_override_pattern"]
    assert FULL["hybrid_override_pattern"] == whole[:11] == "MEMEMEM*EME"
    # every run of 11 layers from a multiple of 11 holds the model's ratio
    for s in range(0, 88, 11):
        part = whole[s:s + 11]
        assert (part.count("M"), part.count("E"), part.count("*")) \
            == (5, 5, 1), s
    d = reference_branch.dims(FULL)
    assert d["kinds"].count("mamba") == 5 and d["kinds"].count("moe") == 5
    assert (d["G"], d["H"], d["P"], d["N"]) == (8, 128, 64, 128)
    assert (d["E"], d["k"], d["held"], d["latent"]) == (512, 22, 128, 1024)


def test_copied_arithmetic_against_brute_force_counts(tiny_branch):
    """``arith_branch`` against the real parameter tree and against counts
    made position by position."""
    dec, params = tiny_branch
    d = branch_cell.dims(TINY)
    assert arith_branch.param_count(d) == sum(
        int(np.prod(v.shape)) for v in params.values()) == dec.num_params()
    full = branch_cell.dims(FULL)
    assert arith_branch.param_count(full) == 4648163712    # 4.65 B: 9.30 GB
    assert arith_branch.expert_params(full) == 5505024
    assert branch_cell.describe(FULL).num_params() == 4648163712
    # one token at context c: every matrix it meets, 2 operations a
    # parameter (a held expert at the uniform router's share), the
    # attention layers' keys, the state layers' states
    c = 29
    mats = 0.0
    for k, v in params.items():
        if k.endswith("_weight") and "tok_embed" not in k \
                and "conv" not in k:
            n = 2 * int(np.prod(v.shape))
            mats += n * 3 / 16 if "_experts_" in k else n
    attn = 2 * 4 * 4 * 16 * c
    state = 2 * 4 * 4 * 16 * 16
    assert arith_branch.token_flops(d, c) == pytest.approx(
        mats + attn + state)
    assert arith_branch.held_picks_expected(d, 16) == 16 * 3 * 8 / 16
    for first, end in ((0, 5), (0, 30), (7, 19)):
        brute = sum(arith_branch.token_flops(d, p + 1, head=False)
                    for p in range(first, end)) \
            + 2 * d["vocab"] * d["d_model"]
        assert arith_branch.prefill_flops(d, end - first, end) \
            == pytest.approx(brute, rel=1e-12), (first, end)
    steps = [(1.0, 2, 120, [(7, 19)], 0, 2, 0)]
    assert arith_branch.window_flops(d, steps) == pytest.approx(
        2 * arith_branch.token_flops(d, 0) + 2 * 4 * 4 * 16 * 120
        + arith_branch.prefill_flops(d, 7, 19))
    # bytes: an expert's two matrices; a pick's rows at both widths
    assert arith_branch.moe_bytes(d, 3, 10) \
        == 2 * (3 * 2 * 16 * 24 + 10 * 2 * (16 + 24))
    # granite's arithmetic holds through what the cell hands its readers
    hybrid, _ = branch_cell.sibling_dims(d)
    assert arith_hybrid.ssm_update_bytes(hybrid, 3) \
        == 3 * 2 * 4 * 16 * 16 * 4 * 2
    assert arith_hybrid.paged_kv_bytes(hybrid, 100) \
        == arith_branch.paged_kv_bytes(d, 100)
    assert arith_branch.state_bytes(d) \
        == 2 * (4 * 16 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 2)
    assert arith_branch.paged_kv_bytes(d, 100) == 100 * 2 * 2 * 16 * 2 * 2
    # the full size, as ISSUE 36 reckons it: a 64-row decode step moves
    # 11.4 GB, 13.9-14.0 ms at 819 GB/s
    step = arith_branch.decode_step_bytes(full, 64, 64 * 1500, 5 * 128 * 0.94)
    assert 11.3e9 < step < 11.5e9


def test_the_branch_weights_distributions_are_pinned(tiny_branch):
    """What the limits of ``correct`` were read on."""
    dec, params = tiny_branch
    big = branch_cell.make_params(
        branch_cell.describe(dict(TINY, hidden_size=256, mamba_head_dim=128,
                                  n_routed_experts=512,
                                  num_experts_held=8)), "float32", 7)
    n_qk = (dec.num_heads + dec.kv_heads) * dec.head_dim
    for name, w in big.items():
        w = np.asarray(w)
        if name.endswith("gamma") or name.endswith("_D"):
            assert (w == 1).all()
        elif name.endswith("_A_log"):
            assert 0 <= w.min() and w.max() <= np.log(16) + 1e-6
        elif name.endswith("_dt_bias"):
            dt = np.log1p(np.exp(w))
            assert 1e-3 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6
        elif name.endswith("_conv_bias"):
            assert w.std() == pytest.approx(0.1, rel=0.15)
        elif name.endswith("_router_bias"):
            assert w.dtype == np.float32
            assert w.std() == pytest.approx(branch_cell.BIAS_STD, rel=0.1)
        else:
            fan_in = w.shape[-2] if "_experts_" in name else w.shape[-1]
            if name.endswith("qkv_weight"):
                assert w[:n_qk].std() * fan_in ** 0.5 == pytest.approx(
                    2 ** 0.5, rel=0.05)
                w = w[n_qk:]
            assert w.std() * fan_in ** 0.5 == pytest.approx(1.0, rel=0.07), \
                name
    again = branch_cell.make_params(dec, "float32", 3600000001)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in params.items())
    other = branch_cell.make_params(dec, "float32", 3600000002)
    assert not (np.asarray(other["branch_head_weight"])
                == np.asarray(params["branch_head_weight"])).all()
    bf = branch_cell.make_params(dec, "bfloat16", 5)
    assert {str(v.dtype) for k, v in bf.items()
            if k.endswith(("_dt_bias", "_A_log", "_D", "_router_bias"))} \
        == {"float32"}


def test_the_selection_bias_moves_picks_and_no_weight(tiny_branch):
    """The bias the benchmark draws is exercised: with 22 of 512 picks a
    row at the full router's width some picks of most rows change, and the
    weights are the picks' own sigmoids whatever the bias is."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    cfg = dict(TINY, n_routed_experts=512, num_experts_per_tok=22,
               num_experts_held=128, expert_offset=0)
    dec = branch_cell.describe(cfg)
    params = branch_cell.make_params(dec, "float32", 11)
    with_bias, _ = reference_branch.router(cfg, params, 1, u)
    without, _ = reference_branch.router(cfg, params, 1, u, fault="no_bias")
    moved = (np.sort(np.asarray(with_bias), -1)
             != np.sort(np.asarray(without), -1)).any(-1)
    assert 0.2 < moved.mean() <= 1.0


@pytest.fixture(scope="module")
def served(tiny_branch):
    """Two requests through a tiny engine: what ``check`` compares."""
    dec, params = tiny_branch
    eng = branch_cell.engine(TINY, dec, params)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, TINY["vocab_size"], n),
                       max_new_tokens=g) for n, g in ((40, 30), (12, 10))]
    while not reqs[1].done:                 # stop with the first running
        eng.step()
    eng.stats()                             # read the pass in flight
    probe = eng.routed_probe()

    class Rec:
        def __init__(self, req):
            self.req = req
            self.finish_t = 1.0 if req.done else None
            self.failed = False

    recs = [Rec(r) for r in reqs]
    done = [r for r in recs if r.finish_t is not None]
    live = [r for r in recs if r.finish_t is None]
    assert len(done) == 1 and len(live) == 1
    states = branch_cell.slot_states(eng, live)
    eng.shutdown()
    return done, live, states, probe


def test_each_control_moves_its_own_number_at_tiny_size(tiny_branch, served):
    """The same sampled requests, states and probe rows against the sound
    reference and each faulty one: the sound reading is rounding, every
    fault moves the number it is meant for."""
    dec, params = tiny_branch
    done, live, states, probe = served
    spec = dict(TINY_MIX["check"], finished=1, live=1)

    def check(**kw):
        return branch_cell.check(params, TINY, spec, done, live, states,
                                 probe, **kw)

    sound = check()
    assert sound["ok"] and sound["mean_regret"] < 1e-5
    assert sound["state_err"] < 1e-5 and sound["state_f32_share"] > 0.99
    assert sound["ffn_err"] < 1e-5 and sound["u_err"] < 1e-5
    assert sound["pick_flips"] == [0, 4]
    for kind in ("decode", "span"):
        assert sound["ffn_err_by_pass"][kind]["rows"] >= 4
    # the state's groups and the norm's: in front of the first routed
    # block, so u_err reads them; one_group also moves the states
    for fault in ("one_group", "norm_whole"):
        v = check(fault=fault)
        assert v["u_err"] > 1e-2 and not v["ok"], fault
        assert v["ffn_err"] < 1e-5, fault
    assert check(fault="one_group")["state_err_first"] > 0.1
    assert check(fault="norm_whole")["state_err"] > 1e-3   # layer 4's input
    # a wrong head mapping in the attention: the regrets, and the inputs
    # of the routed blocks behind it; nothing in front of it
    v = check(fault="kv_swapped")
    assert v["mean_regret"] > 1e-3 and not v["ok"]
    behind = [i > TINY["hybrid_override_pattern"].index("*")
              for i, c in enumerate(TINY["hybrid_override_pattern"])
              if c == "E"]
    assert all((e > 1e-3) == b for e, b in zip(v["u_err_by_layer"], behind))
    assert v["state_err_first"] < 1e-5 and v["ffn_err"] < 1e-5
    # the routed block's own: ffn_err, on the engine's own rows
    for fault in ("relu_plain", "scale_1", "no_bias", "acc_bf16"):
        v = check(fault=fault)
        assert v["ffn_err"] > 100 * sound["ffn_err"], fault
    for fault in ("relu_plain", "scale_1", "no_bias"):
        assert not check(fault=fault)["ok"], fault
    # a state rounded to bfloat16 at every token: the states' distance
    # (the limit sits above bfloat16 ACTIVATIONS' rounding, which this
    # float32 engine has none of: only the chip's readings say whether it
    # lies beyond it, PERF.md)
    v = check(fault="state_bf16")
    assert v["state_err_first"] > 100 * sound["state_err_first"]
    assert v["ffn_err"] < 1e-5
    # an engine's pool rounded to 16 bits fails by state_f32_share alone
    import jax

    rounded = [jax.lax.reduce_precision(S, 8, 7) for S in states]
    v = branch_cell.check(params, TINY, spec, done, live, rounded, probe)
    assert v["state_f32_share"] == 0.0 and not v["ok"]
    few = branch_cell.check(params, TINY, dict(spec, live=2), done, live,
                            states, probe)
    assert not few["ok"] and "fewer than" in few["why"]
    gone = branch_cell.check(params, TINY, spec, done, live, states,
                             dict(probe, decode_rids=[]))
    assert not gone["ok"] and "last decode pass" in gone["why"]


def test_the_controls_ride_one_run_of_the_cell():
    """``branch_controls.run_variants``: the cell's own result (a closed
    loop whose verdict does not depend on how much the second got done),
    then a verdict per variant on what that run sampled;
    ``branch_cell.run`` is put back."""
    res, verdicts = branch_controls.run_variants(
        MANIFEST, CELL, TINY, TINY_MIX, 2 ** 31 + 9, 1.0,
        ["one_group", "scale_1"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_ms_p90", "setup_s"}
    assert set(verdicts) == {"one_group", "scale_1"}
    assert not verdicts["one_group"]["ok"] and not verdicts["scale_1"]["ok"]
    assert branch_cell.run.__module__ == "branch_cell"
    with pytest.raises(SystemExit):
        branch_controls.run_variants(MANIFEST, CELL, TINY, TINY_MIX, 3, 1.0,
                                     ["nonsense"])


def _read(name, ctx):
    return run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m").read(ctx)


def test_branch_readers_on_a_made_up_context(monkeypatch):
    import span_readers

    d = branch_cell.dims(FULL)
    hybrid, moe = branch_cell.sibling_dims(d)
    peaks = (197e12, 819e9)
    steps = [(1.0, 64, 96000, [], 0, 64, 0), (2.0, 64, 96064, [], 0, 64, 0)]
    args = {"moe_picks": 7040, "moe_picks_held": 1760, "moe_load_max": 40,
            "moe_experts_hit": 600, "batch": 64}
    spans = [("serve.decode", i, 0, t, t + 0.01, dict(args))
             for i, t in enumerate((0.6, 1.6))]
    spans += [("serve.step", 9, 0, 0.5, 1.0, {"state_slots": 48})]
    monkeypatch.setattr(span_readers, "in_window",
                        lambda ctx, prefix="serve.": [
                            s for s in spans if s[0].startswith(prefix)])
    ops = [("jit_decode", "pallas_call:tpu_custom_call bf16[1792,2688]",
            0.012, True),
           ("jit_decode", "ssm_state_update:tpu_custom_call", 0.008, True),
           ("jit_decode", "paged_attention_packed:tpu_custom_call", 0.001,
            True),
           ("jit_decode", "fusion:kLoop bf16[64,4096]", 0.001, False)]
    ctx = {"branch": d, "hybrid": hybrid, "moe": moe, "peaks": peaks,
           "steps": steps, "trace_span": (0.5, 2.5),
           "window": {"window_s": 2.0, "start": 0.0, "end": 3.0},
           "trace": {"ops": ops, "modules": {"jit_decode": [0.017, 0.017]},
                     "devices": 1, "busy_s": 1.0}}
    expert = 2 * 1024 * 2688
    need_s = 2 * (600 * expert * 2 + 1760 * 2 * (1024 + 2688) * 2) / 819e9
    assert _read("kernel.moe_latent_roofline", ctx) \
        == pytest.approx(100 * need_s / 0.012)
    states = 128 * 5 * 128 * 64 * 128 * 4 * 2
    assert _read("kernel.ssm_update_roofline", ctx) \
        == pytest.approx(100 * states / 819e9 / 0.008)
    kv = (96000 + 96064) * 2 * 2 * 128 * 2
    assert _read("kernel.paged_packed_roofline", ctx) \
        == pytest.approx(100 * kv / 819e9 / 0.001)
    need = sum(arith_branch.decode_step_bytes(d, 64, c, 600)
               for c in (96000, 96064))
    assert _read("program.decode_mbu.reason", ctx) \
        == pytest.approx(100 * need / 819e9 / 0.034)
    assert _read("program.mfu.reason", ctx) == pytest.approx(
        100 * arith_branch.window_flops(d, steps) / (2.0 * 197e12))
    # the siblings' readers hold through what the cell hands them
    assert _read("state.slot_util_mean", ctx) == pytest.approx(75.0)
    assert _read("moe.experts_hit_share", ctx) \
        == pytest.approx(100 * 600 / (128 * 5))
    assert _read("moe.load_max_over_mean", ctx) \
        == pytest.approx(80 / (3520 / 128))
    for name in ("kernel.moe_latent_roofline", "program.decode_mbu.reason",
                 "program.mfu.reason"):
        assert _read(name, {"moe": {}, "hybrid": {}, "peaks": peaks}) \
            is None, name


def test_the_latent_experts_time_is_the_conditional_where_one_encloses_them():
    reader = run_mod.load_module(os.path.join(
        BENCH, "layer_metrics", "kernel.moe_latent_roofline.py"), "m")
    ops = [("jit_decode", "gmm:tpu_custom_call bf16[1408,2688]", 0.5, True),
           ("jit_decode", "gmm:tpu_custom_call bf16[1408,1024]", 0.3, True),
           ("jit_decode", "fusion:kLoop f32[64,1024]", 0.1, False),
           ("jit_prefill", "conditional f32[2048,1024]", 0.19, False),
           ("jit_prefill", "gmm:tpu_custom_call bf16[22528,2688]", 0.09, True),
           ("jit_prefill", "conditional f32[2048,4096]", 0.4, False),
           ("jit_other", "conditional f32[8,1024]", 9.0, False)]
    assert reader.layer_seconds(ops, 1024) == pytest.approx(0.8 + 0.19)


def test_the_mix_is_data_over_what_is_there():
    assert FULL_MIX["loop"] == "closed" and FULL_MIX["clients"] == 80
    assert FULL_MIX["prompt"] == {"dist": "loguniform", "min": 128,
                                  "max": 2048}
    assert FULL_MIX["output"] == {"dist": "cycle",
                                  "values": [512, 768, 1024, 1280, 1536]}
    assert FULL_MIX["shapes"] == 128 and FULL_MIX["ramp_finished"] >= 80
    assert FULL["engine"]["max_batch"] == 64 < FULL_MIX["clients"]
    assert 2048 + 1536 <= FULL["engine"]["max_model_len"]
    assert FULL_MIX["check"]["max_len"] >= 2048 + 1536
    assert FULL["engine"]["num_blocks"] == 64 * 4096 // 16 + 1
    # no chunk pass: every prompt fits the prefill chunk whole
    assert FULL_MIX["prompt"]["max"] <= FULL["engine"]["prefill_chunk"]
