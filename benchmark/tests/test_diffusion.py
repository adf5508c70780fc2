"""The diffusion cell's own files, on the CPU at the tiny size: the
benchmark's copy of the reference against the program's, the copied
arithmetic against hand counts, the weights' distributions,
``serve_cell.drive``'s stamps of block-at-a-time tokens, the comparison behind ``correct`` on
requests an engine served (and against references that are not the
configuration's: ``diffusion_controls``), and the readers on made-up
contexts.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_diffusion.py -q
"""

import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import arith_diffusion       # noqa: E402
import diffusion_cell        # noqa: E402
import diffusion_controls    # noqa: E402
import reference_diffusion   # noqa: E402
import run as run_mod        # noqa: E402
import serve_cell            # noqa: E402
import traffic               # noqa: E402

CONFIG = "sdar-30b-a3b-l6"
CELL = CONFIG + ".reason-blocks"
MANIFEST = run_mod.load_json(ROOT, "BENCHMARK.json")
TINY = run_mod.load_json(HERE, "tiny", "configs", CONFIG + ".json")
TINY_MIX = run_mod.load_json(HERE, "tiny", "traffic", "reason-blocks.json")
FULL = run_mod.load_json(BENCH, "configs", CONFIG + ".json")
FULL_MIX = run_mod.load_json(BENCH, "traffic", "reason-blocks.json")
SEED = 4200000007


@pytest.fixture(scope="module")
def tiny():
    dec = diffusion_cell.describe(TINY)
    return dec, diffusion_cell.make_params(dec, "float32", SEED)


@pytest.fixture(scope="module")
def served(tiny):
    """A tiny engine's requests, served to the end and mid-way, as the
    cell snapshots them, and its probe."""
    import mxnet_tpu as mx

    dec, params = tiny
    eng = diffusion_cell.engine(TINY, dec, params)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(traffic.token_ids(rng, n, TINY["text_ids"]),
                       max_new_tokens=g)
            for n, g in ((13, 10), (22, 7), (9, 30), (30, 30))]
    while not all(r.done for r in reqs[:2]):
        eng.step()
    probe = eng.routed_probe()
    recs = []
    for r in reqs:
        rec = serve_cell.Rec(r, 0.0, 0.0)
        rec.finish_t = 1.0 if r.done else None
        recs.append(rec)
    seen = diffusion_cell.snapshot(eng, recs)
    span = diffusion_cell.span_prompt({"all": recs}, probe)
    eng.shutdown()
    assert isinstance(eng, mx.serve.Engine) and span is not None
    return seen, probe, span


def test_the_diffusion_reference_equals_the_programs(tiny):
    """Two independent writings of the same equations on seeded weights,
    float32 both: the replayed steps' tokens are the program reference's
    own greedy tokens (regret 0), and its order (order regret 0)."""
    dec, params = tiny
    prompt = np.random.default_rng(0).integers(0, TINY["text_ids"], 11)
    gen = dec.reference_generate(params, prompt, 9)
    ref = reference_diffusion.replay(TINY, params, prompt, gen["unmasked"])
    assert ref["schedule_ok"]
    assert ref["sequence"][:9] == gen["tokens"]
    assert max(ref["regrets"]) < 1e-4 and max(ref["order_regrets"]) < 1e-5
    assert ref["steps"] == len(gen["steps"])
    assert 2.0 < ref["logit_std"] < 6.0         # HEAD_GAIN's spread


# the source's config.json (FULL["source"]), every number and flag
SOURCE = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
          "hidden_act": "silu", "hidden_size": 2048,
          "intermediate_size": 6144, "max_position_embeddings": 32768,
          "max_window_layers": 48, "mlp_only_layers": [],
          "model_type": "sdar_moe", "moe_intermediate_size": 768,
          "norm_topk_prob": True, "num_attention_heads": 32,
          "num_experts": 128, "num_experts_per_tok": 8,
          "num_hidden_layers": 48, "num_key_value_heads": 4,
          "rms_norm_eps": 1e-06, "rope_scaling": None,
          "rope_theta": 1000000, "sliding_window": None,
          "tie_word_embeddings": False, "use_sliding_window": False,
          "vocab_size": 151936}


def test_the_full_configuration_is_the_sources_but_for_its_cuts():
    """Every number of the source's config under the same key, but the
    keys ``reduced`` names; the cut is whole layers of a one-layer
    period."""
    reduced = set(FULL["reduced"])
    assert reduced == {"num_hidden_layers", "max_position_embeddings"}
    for k, v in SOURCE.items():
        if k in reduced:
            assert FULL[k] != v and FULL["published"][k] == v, k
        else:
            assert FULL[k] == v, k
    assert FULL["source"] == ("https://huggingface.co/JetLM/"
                              "SDAR-30B-A3B-Chat/blob/main/config.json")
    d = reference_diffusion.dims(FULL)
    assert (d["L"], d["H"], d["Hkv"], d["Dh"], d["E"], d["k"], d["F"]) \
        == (6, 32, 4, 128, 128, 8, 768)
    assert (d["B"], d["T"], d["mask"]) == (4, 4, 151669)
    assert FULL["text_ids"] <= d["mask"] < d["V"]
    assert set(FULL["assumed"]) >= {"block_length", "denoising_steps",
                                    "remasking", "mask_token_id",
                                    "qk_norm", "shift"}
    # the engine's geometry holds the longest requests of a full batch
    geo = FULL["engine"]
    assert geo["prefill_chunk"] % d["B"] == 0
    assert (geo["num_blocks"] - 1) * geo["block_size"] \
        >= geo["max_batch"] * FULL_MIX["check"]["max_len"]


def test_copied_arithmetic_against_hand_counts():
    d = diffusion_cell.dims(FULL)
    D, F, E, V = 2048, 768, 128, 151936
    attn = (32 + 8) * 128 * D + D * 32 * 128            # 18.87 M
    assert arith_diffusion.attn_params(d) == attn == 18874368
    assert arith_diffusion.expert_params(d) == 3 * D * F == 4718592
    layer = attn + E * D + E * 3 * D * F + 2 * D + 2 * 128
    assert arith_diffusion.param_count(d) == 2 * V * D + D + 6 * layer
    assert 4.35e9 < arith_diffusion.param_count(d) < 4.37e9
    assert arith_diffusion.kv_bytes_per_position(d) == 6 * 2 * 4 * 128 * 2
    # one position at no context: 6 layers of attention, router and 8
    # experts, and the head
    one = 6 * (2 * (attn + E * D) + 2 * 8 * 3 * D * F) + 2 * V * D
    assert arith_diffusion.position_flops(d, 0) == one
    assert arith_diffusion.denoise_flops(d, 64, 1000) \
        == 256 * one + 6 * 4 * 32 * 128 * 4 * 1000
    # a prefill of 8 positions from 0: block-causal, each sees its block
    keys = 4 * 4 + 4 * 8
    assert arith_diffusion.prefill_flops(d, 8, 8) == (
        8 * arith_diffusion.position_flops(d, 0, head=False)
        + 6 * 4 * 32 * 128 * keys + 2 * V * D)
    fixed = arith_diffusion.param_count(d) - 6 * E * 3 * D * F - V * D
    assert arith_diffusion.denoise_bytes(d, 64, 1000, 768) == (
        fixed * 2 + 768 * 3 * D * F * 2 + 1000 * 12288 + 256 * 12288)


def test_the_diffusion_weights_distributions_are_pinned(tiny):
    """What the limits of ``correct`` were read on."""
    dec, params = tiny
    big = diffusion_cell.make_params(
        diffusion_cell.describe(dict(TINY, hidden_size=256,
                                     moe_intermediate_size=128,
                                     vocab_size=512, mask_token_id=511)),
        "float32", 7)
    for name, w in big.items():
        w = np.asarray(w)
        if name.endswith(("_q_norm_gamma", "_k_norm_gamma")):
            assert np.allclose(w, diffusion_cell.QK_GAIN)
        elif name.endswith("gamma"):
            assert (w == 1).all()
        else:
            fan_in = w.shape[-2] if "_experts_" in name else w.shape[-1]
            gain = (diffusion_cell.HEAD_GAIN
                    if name.endswith("_head_weight") else 1.0)
            assert w.std() * fan_in ** 0.5 == pytest.approx(gain, rel=0.07), \
                name
    again = diffusion_cell.make_params(dec, "float32", SEED)
    assert all((np.asarray(again[k]) == np.asarray(v)).all()
               for k, v in params.items())
    other = diffusion_cell.make_params(dec, "float32", SEED + 1)
    assert not (np.asarray(other["moe_head_weight"])
                == np.asarray(params["moe_head_weight"])).all()


def test_every_request_gets_a_first_token_stamp(tiny):
    """Tokens come a block at a time with the store pass, and
    ``cache_len`` grows by the block in the same step: ``drive`` stamps
    every request's first token, and its gaps."""
    dec, params = tiny
    eng = diffusion_cell.engine(TINY, dec, params)
    loop = traffic.loop(TINY_MIX, 3, 1.0, TINY["text_ids"])
    eng.warmup([{"kind": k, "bucket": b} for k, b in diffusion_cell.programs(
        loop.prompt_len, TINY["engine"], TINY["block_length"])])
    out = serve_cell.drive(eng, loop, 1.0)
    served = [r for r in out["all"] if r.seen]
    assert served and all(r.first_t is not None for r in served)
    done = [r for r in out["all"] if r.finish_t is not None and not r.failed]
    assert done and all(r.seen == r.req.max_new_tokens for r in done)
    assert all(r.tpot(out["end"]) is not None for r in served
               if r.seen > 1)
    eng.shutdown()


def test_the_tiny_twin_fits_the_contract_window():
    """The contract test runs the tiny twin in a 1 s window under the
    suite's load: its set-up, window and check stay a few seconds."""
    tic = time.perf_counter()
    res = run_mod.run_cell(MANIFEST, CELL, TINY, TINY_MIX, 2 ** 31 + 11,
                           1.0, 0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_ms_p90", "setup_s"}
    assert time.perf_counter() - tic < 60


def test_the_check_holds_on_what_an_engine_served(tiny, served):
    dec, params = tiny
    seen, probe, span = served
    spec = dict(TINY_MIX["check"], finished=2, live=2)
    v = diffusion_cell.check(params, TINY, spec, seen, probe, span)
    assert v["ok"], v
    assert v["u_err_by_pass"]["span"] < 1e-5
    assert v["max_regret"] < 1e-3 and v["order_regret"] < 1e-4
    assert v["kv_err"] < 1e-5 and v["u_err"] < 1e-5 and v["ffn_err"] < 1e-5
    assert v["schedule_ok"] and v["tokens_match"] and v["probed"] >= 1


@pytest.mark.parametrize("fault,moves", [
    ("causal_in_block", "max_regret"), ("causal_in_block", "u_err"),
    ("no_qk_norm", "max_regret"),
    ("shift", "max_regret"), ("left_to_right", "order_regret"),
    ("stale_kv", "kv_err"), ("acc_bf16", "ffn_err"),
    ("router_bf16", "ffn_err")])
def test_each_control_moves_its_own_number(tiny, served, fault, moves):
    """The controls enter as data of the comparison: each reference fault
    moves the number that tells it, on the same served requests."""
    dec, params = tiny
    seen, probe, span = served
    spec = dict(TINY_MIX["check"], finished=2, live=2)
    sound = diffusion_cell.check(params, TINY, spec, seen, probe, span)
    v = diffusion_cell.check(params, TINY, spec, seen, probe, span,
                             fault=fault)
    assert v[moves] > 10 * max(sound[moves], 1e-6), (fault, v[moves])
    if moves != "ffn_err":
        assert not v["ok"], fault


def test_a_wrong_record_is_not_correct(tiny, served):
    """The record itself is held: a token the request got that its blocks
    do not hold, or a step out of the static schedule."""
    dec, params = tiny
    seen, probe, _ = served
    spec = dict(TINY_MIX["check"], finished=2, live=2)
    bad = [dict(s) for s in seen]
    bad[0]["tokens"] = list(bad[0]["tokens"])
    bad[0]["tokens"][0] = (bad[0]["tokens"][0] + 1) % TINY["text_ids"]
    v = diffusion_cell.check(params, TINY, spec, bad, probe)
    assert not v["ok"] and not v["tokens_match"]
    bad = [dict(s) for s in seen]
    bad[0]["unmasked"] = [(p, t, 0) for p, t, _ in bad[0]["unmasked"]]
    v = diffusion_cell.check(params, TINY, spec, bad, probe)
    assert not v["ok"] and not v["schedule_ok"]


def test_the_controls_ride_one_run_of_the_cell():
    res, verdicts = diffusion_controls.run_variants(
        MANIFEST, CELL, TINY, TINY_MIX, 2 ** 31 + 9, 1.0,
        ["shift", "stale_kv"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(verdicts) == {"shift", "stale_kv"}
    assert not verdicts["shift"]["ok"] and not verdicts["stale_kv"]["ok"]
    assert diffusion_cell.run.__module__ == "diffusion_cell"
    with pytest.raises(SystemExit):
        diffusion_controls.run_variants(MANIFEST, CELL, TINY, TINY_MIX, 3,
                                        1.0, ["nonsense"])


def _read(name, ctx):
    return run_mod.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), "m").read(ctx)


def test_diffusion_readers_on_a_made_up_context(monkeypatch):
    import span_readers

    d = diffusion_cell.dims(FULL)
    peaks = (197e12, 819e9)
    dec_args = {"batch": 64, "bucket": 64, "block_step": "13,13,13,13,12",
                "unmasked": 52, "committed": 12, "contexts": 80000,
                "moe_picks": 12288, "moe_picks_held": 12288,
                "moe_load_max": 40, "moe_experts_hit": 768}
    spans = [("serve.decode", i, 0, t, t + 0.01, dict(dec_args))
             for i, t in enumerate((0.6, 1.6))]
    spans += [("serve.prefill", 7, 0, 1.7, 1.71,
               {"tokens": 512, "start": 1024, "bucket": 512})]
    monkeypatch.setattr(span_readers, "in_window",
                        lambda ctx, prefix="serve.": [
                            s for s in spans if s[0].startswith(prefix)])
    ops = [("jit_decode", "paged_attention:tpu_custom_call", 0.002, True),
           ("jit_decode", "gmm:tpu_custom_call bf16[2048,1536]", 0.01, True),
           ("jit_decode", "fusion:kLoop bf16[256,2048]", 0.001, False)]
    ctx = {"diffusion": d, "moe": diffusion_cell.sibling_dims(d),
           "peaks": peaks, "trace_span": (0.5, 2.5),
           "window": {"window_s": 2.0, "start": 0.0, "end": 3.0},
           "trace": {"ops": ops, "modules": {"jit_decode": [0.015, 0.015]},
                     "devices": 1, "busy_s": 1.0}}
    assert _read("sched.passes_per_block", ctx) == pytest.approx(128 / 24)
    kv = 2 * 80000 * 12288
    assert _read("kernel.paged_block_roofline", ctx) \
        == pytest.approx(100 * kv / 819e9 / 0.002)
    need = 2 * arith_diffusion.denoise_bytes(d, 64, 80000, 768)
    assert _read("program.decode_mbu.blocks", ctx) \
        == pytest.approx(100 * need / 819e9 / 0.03)
    ops_n = 2 * arith_diffusion.denoise_flops(d, 64, 80000) \
        + arith_diffusion.prefill_flops(d, 512, 1536)
    assert _read("program.mfu.blocks", ctx) \
        == pytest.approx(100 * ops_n / (2.0 * 197e12))
    # the accepted readers of routed experts hold through ctx["moe"]
    assert _read("moe.experts_hit_share", ctx) \
        == pytest.approx(100 * 768 / (128 * 6))
    assert _read("moe.load_max_over_mean", ctx) \
        == pytest.approx(80 / (24576 / 128))
    for name in ("kernel.paged_block_roofline", "program.decode_mbu.blocks",
                 "program.mfu.blocks"):
        assert _read(name, {"peaks": peaks}) is None, name


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """On the parent's program (no ``committed`` or ``contexts`` on its
    passes) the new readers return nothing and raise nothing."""
    import span_readers

    spans = [("serve.decode", 1, 0, 0.6, 0.61, {"batch": 64, "bucket": 64}),
             ("serve.prefill", 2, 0, 0.7, 0.71, {"tokens": 8, "bucket": 8})]
    monkeypatch.setattr(span_readers, "in_window",
                        lambda ctx, prefix="serve.": [
                            s for s in spans if s[0].startswith(prefix)])
    d = diffusion_cell.dims(FULL)
    ctx = {"diffusion": d, "peaks": (197e12, 819e9),
           "trace_span": (0.5, 2.5), "window": {"window_s": 2.0},
           "trace": {"ops": [], "modules": {}, "devices": 1, "busy_s": 1.0}}
    for name in ("sched.passes_per_block", "kernel.paged_block_roofline",
                 "program.decode_mbu.blocks", "program.mfu.blocks"):
        assert _read(name, ctx) is None, name


def test_the_programs_cover_whole_block_prefills_and_chunks():
    """Every prefill and chunk bucket a tiny engine runs is in the list
    the cell warms (a prefill covers whole blocks; a chunk's budget falls
    by a block a decoding row)."""
    geo = TINY["engine"]
    lens = list(range(4, 41))
    progs = set(diffusion_cell.programs(lens, geo, 4))
    assert ("prefill", 4) in progs and ("prefill", 16) in progs
    assert ("prefill", 32) not in progs          # past the chunk: chunked
    assert {b for k, b in progs if k == "decode"} == {1, 2, 4}
    for span in range(4, geo["prefill_chunk"] + 1, 4):
        assert ("chunk", serve_cell.next_bucket(
            span, serve_cell.next_bucket(geo["prefill_chunk"],
                                         geo["max_model_len"]))) in progs


def test_the_mix_is_data_over_what_is_there():
    assert FULL_MIX["loop"] == "closed" and FULL_MIX["clients"] == 80
    assert FULL_MIX["prompt"] == {"dist": "loguniform", "min": 128,
                                  "max": 2048}
    assert FULL_MIX["output"] == {"dist": "cycle",
                                  "values": [512, 768, 1024, 1280, 1536]}
    assert all(v % FULL["block_length"] == 0
               for v in FULL_MIX["output"]["values"])
    assert FULL_MIX["shapes"] == 128 and FULL_MIX["ramp_finished"] == 240
    assert FULL["engine"]["max_batch"] == 64 < FULL_MIX["clients"]
    assert FULL_MIX["check"]["max_len"] >= 2048 + 1536
