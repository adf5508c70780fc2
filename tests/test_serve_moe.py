"""Routed-expert decoders (window and global attention layers in two block
groups, routed and shared experts told which experts they hold) through
serve.Engine, at a small size on the CPU: two periods of [full, window,
window, window], widths of tens, 16 experts of which 8 are held, window 8,
blocks of 4.  ``models/moe.py::reference_logits`` (float32, no cache, no
kernel) is the yardstick throughout.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import moe as M
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops.attention import (masked_attention, paged_attention,
                                     score_scale)
from mxnet_tpu.serve.kv_block_manager import (BlockManager, NoFreeBlocks,
                                              WindowGroup)
from mxnet_tpu.serve.scheduler import FINISHED

VOCAB, WINDOW, BS = 61, 8, 4


def _dec(held=(4, 8), name="moe"):
    rope = {M.FULL: M.Rope(8, 500000.0, (16.0, 32, 4.0, 1.0, 1.2)),
            M.WINDOW: M.Rope(16, 10000.0, None)}
    return M.moe_decoder(
        VOCAB, 32, [M.FULL, M.WINDOW, M.WINDOW, M.WINDOW] * 2,
        heads=[4, 6, 6, 6] * 2, kv_heads=2, head_dim=16, window=WINDOW,
        ffn_types=["dense"] + ["moe"] * 7, d_ff=48, num_experts=16, top_k=3,
        expert_ff=24, shared_ff=24, routed_scale=2.5, experts_held=held,
        rope=rope, name=name)


@pytest.fixture(scope="module")
def model():
    dec = _dec()
    return dec, dec.init_params(3, "float32")


def _engine(model, **kw):
    dec, params = model
    geo = dict(block_size=BS, num_blocks=64, max_batch=4, max_model_len=64,
               prefill_chunk=16)
    geo.update(kw)
    return mx.serve.Engine(params, symbol=dec, **geo)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


def _regret(model, prompt, tokens):
    dec, params = model
    toks = np.concatenate([prompt, tokens])
    ref = np.asarray(dec.reference_logits(params, toks[:-1]))
    ref = ref[len(prompt) - 1:]
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]


# -- the engine against the reference ------------------------------------------

@pytest.mark.parametrize("plen,new,passes", [
    (5, 6, 1),          # inside one window
    (16, 12, 1),        # a whole prompt two windows long, decode past a third
    (37, 20, 3),        # three chunk passes: each edge lies inside a window
    (21, 30, 2),        # decode crosses the window four times
])
def test_prefill_chunk_and_decode_through_both_groups_equal_the_reference(
        model, plen, new, passes):
    """Logits, not tokens: the reference logit of the engine's greedy token
    is within 1e-4 of the reference's best at every generated position
    (everything float32 on the CPU; the caches reorder sums, no more)."""
    eng = _engine(model)
    prompt = _prompt(plen, plen)
    req = eng.submit(prompt, max_new_tokens=new)
    eng.run()
    assert req.status == FINISHED and len(req.tokens) == new
    assert req.prefill_passes == passes
    assert _regret(model, prompt, np.asarray(req.tokens)).max() <= 1e-4
    groups = eng.statusz()["kv_groups"]
    assert groups["window"]["in_use"] == 0 and groups["global"]["in_use"] == 0
    # decode alone pushes a block out of the window every BS positions
    assert groups["window"]["freed"] >= new // BS - 1
    eng.shutdown()


def test_requests_side_by_side_equal_serving_alone(model):
    """Three requests of different lengths in one batch (padding rows in
    the bucket of 4, one prompt chunked beside two that decode) give the
    tokens each gives alone."""
    prompts = [_prompt(40 + i, n) for i, n in enumerate((7, 33, 18))]
    alone = []
    for p in prompts:
        eng = _engine(model)
        r = eng.submit(p, max_new_tokens=10)
        eng.run()
        alone.append(list(r.tokens))
        eng.shutdown()
    eng = _engine(model)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert [list(r.tokens) for r in reqs] == alone
    eng.shutdown()


def test_a_preempted_request_frees_both_groups_and_resumes(model):
    """A cache too small for both requests' whole contexts: one is
    preempted (both groups freed), prefilled anew and ends with the tokens
    it gives alone."""
    eng = _engine(model, num_blocks=14)
    prompts = [_prompt(50 + i, 20) for i in range(2)]
    reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run()
    assert eng.scheduler.preemptions >= 1
    for p, r in zip(prompts, reqs):
        assert r.status == FINISHED
        assert _regret(model, p, np.asarray(r.tokens)).max() <= 1e-4
    assert eng.statusz()["kv_groups"]["window"]["requests"] == 0
    eng.shutdown()


def test_a_description_without_window_layers_has_one_group():
    """All layers global, one routed layer among dense ones: no window
    group is built (``kv_groups`` None) and no cache stands in for one:
    the programs take the K/V pair and the probe.  The router's counts
    still ride back."""
    dec = M.moe_decoder(VOCAB, 32, [M.FULL] * 3, heads=[4, 4, 4], kv_heads=2,
                        head_dim=16, window=0,
                        ffn_types=["dense", "moe", "dense"], d_ff=48,
                        num_experts=8, top_k=2, expert_ff=24, shared_ff=24)
    params = dec.init_params(1, "float32")
    eng = mx.serve.Engine(params, symbol=dec, block_size=BS, num_blocks=64,
                          max_batch=2, max_model_len=64, prefill_chunk=16)
    assert eng.blocks.window is None and eng.statusz()["kv_groups"] is None
    assert eng._extra_caches == ("_probe",) and len(eng._cache_args()) == 3
    prompt = _prompt(0, 30)
    req = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert _regret((dec, params), prompt,
                   np.asarray(req.tokens)).max() <= 1e-4
    eng.shutdown()


def test_what_a_description_brings_is_read_off_the_description(model):
    """The programs' caches and operands follow the description's layers,
    not its class: a state pool for state-space layers, a window group
    for window layers, the probe for a routed block."""
    from mxnet_tpu.serve import hybrid as H

    dec, _ = model
    assert H.extra_caches(dec) == ("wk", "wv", "probe")
    hyb = mx.models.hybrid_decoder(
        61, 32, ["mamba", "attention"], num_heads=4, kv_heads=2, d_ff=48,
        mamba_heads=4, mamba_head_dim=16, mamba_state=16, mamba_chunk=8)
    assert H.extra_caches(hyb) == ("ssm", "conv") and not H.routed(hyb)
    assert hyb.window_layers == () and dec.mamba_layers == ()
    assert hyb.global_layers == hyb.attention_layers
    assert dec.global_layers == dec.full_layers
    assert H.probe_layers(dec) == tuple(range(1, 8))
    assert H.probe_shape(dec, 4) == (2, 4, 7, 2, 32)


def test_the_probe_holds_what_the_programs_computed(model):
    """``Engine.routed_probe``: every routed block's input and output
    rows of the newest decode pass and of the newest span, as the serving
    programs computed them, against the reference's routed block on the
    same rows; padding rows are zero; whose rows they were."""
    dec, params = model
    P = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    eng = _engine(model)
    assert not eng.routed_probe()["decode"][0].any()
    reqs = [eng.submit(_prompt(40 + i, 21 + 9 * i), max_new_tokens=6)
            for i in range(3)]
    eng.run()
    probe = eng.routed_probe()
    assert probe["layers"] == tuple(range(1, 8))
    for kind in ("decode", "span"):
        assert probe[kind][0].shape == probe[kind][1].shape == (4, 7, 32)
        for at, layer in enumerate(probe["layers"]):
            u, y = (jnp.asarray(a[:, at]) for a in probe[kind])
            real = np.asarray(jnp.abs(u).sum(-1) > 0)
            # the last decode pass may have had fewer rows than the batch
            assert real.sum() == (4 if kind == "span"
                                  else len(probe["decode_rids"]))
            with jax.default_matmul_precision("highest"):
                want = M.reference_shared(dec, P, layer, u) \
                    + M.reference_routed(dec, P, layer, u)
            np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                       atol=3e-5)
            assert not np.asarray(y)[~real].any()
    # the rows' owners: the last decode pass's requests, in row order, and
    # the newest span's request and first position
    assert set(probe["decode_rids"]) <= {r.rid for r in reqs}
    assert probe["span_rows"][0] in {r.rid for r in reqs}
    # the decode rows' inputs are the reference's at the rows' positions
    for row, rid in enumerate(probe["decode_rids"]):
        req = next(r for r in reqs if r.rid == rid)
        taps = {}
        M.reference_logits(
            dec, params, np.concatenate([req.prompt, req.tokens])[:-1], taps)
        for at, layer in enumerate(probe["layers"]):
            np.testing.assert_allclose(probe["decode"][0][row, at],
                                       np.asarray(taps[layer][-1]),
                                       atol=3e-4)
    assert all(r.status == FINISHED for r in reqs)
    eng.shutdown()
    assert eng._probe is None


# -- the shares add up -----------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight programs that each hold 2 of the 16 experts: their routed
    parts, plus the shared expert counted once, are the whole model's
    layer output (the weights are normalised over all picks in every
    share, so the parts simply add)."""
    whole = _dec(held=None)
    P = {k: jnp.asarray(v, jnp.float32)
         for k, v in whole.init_params(7, "float32").items()}
    u = jnp.asarray(np.random.default_rng(0).normal(size=(19, 32)),
                    jnp.float32)
    i, p = 3, "moe_l3"
    with jax.default_matmul_precision("highest"):
        want = M.reference_shared(whole, P, i, u) \
            + M.reference_routed(whole, P, i, u)
        got = M.reference_shared(whole, P, i, u)
        for s in range(8):
            share = _dec(held=(2 * s, 2))
            Ps = dict(P)
            for stem in ("experts_in_weight", "experts_out_weight"):
                Ps[f"{p}_{stem}"] = P[f"{p}_{stem}"][2 * s:2 * s + 2]
            got = got + M.reference_routed(share, Ps, i, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_serving_layer_computes_its_share_only(model):
    """ops.moe.routed_experts over the held experts against the
    reference's dense one-hot form, padding rows routing nowhere."""
    dec, params = model
    P = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(12, 32)),
                    jnp.float32)
    p = "moe_l2"
    idx, w = moe_ops.route(u @ P[f"{p}_router_weight"].T, dec.top_k)
    valid = jnp.arange(12) < 9
    y, stats = moe_ops.routed_experts(
        u, P[f"{p}_experts_in_weight"], P[f"{p}_experts_out_weight"], idx,
        w, dec.expert_offset, dec.num_experts, valid=valid)
    want = np.asarray(M.reference_routed(dec, P, 2, u)) / dec.routed_scale
    np.testing.assert_allclose(np.asarray(y[:9]), want[:9], atol=2e-5)
    assert not np.asarray(y[9:]).any()
    held = np.logical_and(np.asarray(idx[:9]) >= 4, np.asarray(idx[:9]) < 12)
    counts = np.bincount(np.asarray(idx[:9])[held] - 4, minlength=8)
    assert list(np.asarray(stats)) == [27, held.sum(), counts.max(),
                                       (counts > 0).sum(), 0]


@pytest.mark.parametrize("impl", ["ragged", "pallas"])
def test_dropless_under_a_router_forced_onto_one_expert(impl):
    """Every row picks the same held expert: all 50 rows are computed (no
    capacity, nothing dropped), by either grouped product (the Mosaic
    kernel through the interpreter)."""
    rng = np.random.default_rng(2)
    T, D, F, count = 50, 32, 16, 4
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(count, D, 2 * F)) / 6, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(count, F, D)) / 4, jnp.float32)
    idx = jnp.stack([jnp.full((T,), 6, jnp.int32),       # held: 6 - 4 = 2
                     jnp.full((T,), 13, jnp.int32)], 1)  # absent
    w = jnp.full((T, 2), 0.5, jnp.float32)
    y, stats = moe_ops.routed_experts(x, w_in, w_out, idx, w, 4, 16,
                                      impl=impl)
    gu = np.asarray(x) @ np.asarray(w_in[2])
    want = 0.5 * ((gu[:, :F] / (1 + np.exp(-gu[:, :F])) * gu[:, F:])
                  @ np.asarray(w_out[2]))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert list(np.asarray(stats)) == [2 * T, T, T, 1, 0]


@pytest.mark.parametrize("forced", [False, True])
def test_a_span_takes_the_front_of_the_sorted_picks_or_all_of_them(forced):
    """512 rows x 8 picks = 4096 picks, 8 of 64 experts held: a router
    that favours none leaves about 512 held picks, which fit the first
    1024 sorted picks (the short path); one forced onto held experts
    leaves 4096, which do not (all picks).  Both against a dense count."""
    rng = np.random.default_rng(5)
    T, k, E, D, F, off, count = 512, 8, 64, 32, 16, 16, 8
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(count, D, 2 * F)) / 6, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(count, F, D)) / 4, jnp.float32)
    logits = rng.normal(size=(T, E)).astype(np.float32)
    if forced:
        logits[:, off:off + count] += 10.0
    idx, w = moe_ops.route(jnp.asarray(logits), k)
    assert moe_ops.short_path(T * k, count, E) == 1024
    y, stats = moe_ops.routed_experts(x, w_in, w_out, idx, w, off, E)
    held = int(stats[1])
    assert (held == T * k) if forced else (300 < held < 1024)
    assert int(stats[4]) == (0 if forced else 1)     # which path it took
    want = np.zeros((T, D), np.float32)
    for e in range(count):
        gu = np.asarray(x) @ np.asarray(w_in[e])
        out = (gu[:, :F] / (1 + np.exp(-gu[:, :F])) * gu[:, F:]) \
            @ np.asarray(w_out[e])
        share = np.where(np.asarray(idx) == off + e, np.asarray(w), 0).sum(1)
        want += share[:, None] * out
    np.testing.assert_allclose(np.asarray(y), want, atol=3e-5)


@pytest.mark.parametrize("picks,count,E,want", [
    (20480, 32, 256, 5120),     # a chunk of 2048 rows, an eighth held
    (11520, 32, 256, 2944),     # a bucket of 1152 rows: whole row tiles
    (320, 32, 256, None),       # a decode pass: too little to spare
    (20480, 128, 256, None),    # half held: the margin covers every pick
    (20480, 256, 256, None),    # a whole model's program has one path
])
def test_the_short_path_follows_the_share_held(picks, count, E, want):
    """Twice a uniform router's share, in whole row tiles; one path only
    where that covers every pick or spares too little."""
    assert moe_ops.short_path(picks, count, E) == want


def test_a_whole_models_program_compiles_one_path():
    """With every expert held there is no conditional in the program."""
    rng = np.random.default_rng(6)
    T, k, E, D, F = 512, 8, 8, 32, 16
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(E, D, 2 * F)), jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(E, F, D)), jnp.float32)
    idx, w = moe_ops.route(jnp.asarray(rng.normal(size=(T, E)),
                                       jnp.float32), k)
    text = moe_ops.routed_experts.lower(x, w_in, w_out, idx, w, 0,
                                        E).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    part = moe_ops.routed_experts.lower(x, w_in[:1], w_out[:1], idx, w, 0,
                                        E).as_text()
    assert "stablehlo.case" in part or "stablehlo.if" in part


# -- attention: groups of 6 and 9, two rotary schemes ----------------------------

@pytest.mark.parametrize("Hq,window", [(48, 0), (72, 40)])
def test_paged_kernel_serves_groups_of_6_and_9(Hq, window):
    """48 / 8 and 72 / 8 heads of 128 through the Mosaic paged kernel
    (interpreter) against the dense gather form, the window group's table
    holding the null block behind the window."""
    rng = np.random.default_rng(Hq)
    B, Hkv, Dh, bs, W = 3, 8, 128, 16, 6
    q = jnp.asarray(rng.normal(size=(B, Hq, Dh)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(2, 24, bs, Hkv, Dh)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(2, 24, bs, Hkv, Dh)), jnp.float32)
    ctx = np.asarray([90, 17, 64], np.int32)
    tables = np.zeros((B, W), np.int32)
    free = iter(range(1, 24))
    for b in range(B):
        lo = max(ctx[b] - window, 0) // bs if window else 0
        for j in range(lo, -(-ctx[b] // bs)):
            tables[b, j] = next(free)
    kw = dict(layer=1, window=window)
    want = paged_attention(q, ck, cv, jnp.asarray(tables), jnp.asarray(ctx),
                           impl="jnp", **kw)
    got = paged_attention(q, ck, cv, jnp.asarray(tables), jnp.asarray(ctx),
                          impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("Hq,window", [(48, 0), (72, 48)])
def test_span_kernel_serves_groups_of_6_and_9(Hq, window):
    from mxnet_tpu.ops.pallas_span_attention import span_attention_kernel

    rng = np.random.default_rng(Hq + 1)
    T, S, Hkv, Dh, start, n = 64, 256, 8, 128, 130, 50
    q = jnp.asarray(rng.normal(size=(T, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, Hkv, Dh)), jnp.float32)
    want = masked_attention(q, k, v, start, score_scale(Dh), window=window)
    got = span_attention_kernel(q, k, v, jnp.int32(start), jnp.int32(n),
                                score_scale(Dh), window=window, block_q=32,
                                block_k=128, resident=S, interpret=True)
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               atol=2e-5, rtol=2e-5)


def test_yarn_inverse_frequencies_at_the_published_keys():
    """Rope(64, 500000, (128, 8192, 32, 1, 1.4852...)): the correction
    dimensions are 64 ln(8192 / (2 pi n)) / (2 ln 500000) = 9.04 at n = 32
    and 17.49 at n = 1, so dimensions 0-9 keep 1 / b^(2j/64), 18-31 are
    divided by 128, and 10-17 are blended by (j - 9) / 9.  Values computed
    by hand (python floats) from that sentence."""
    rope = M.Rope(64, 500000.0, (128.0, 8192, 32.0, 1.0, 1.4852030263919618))
    inv = rope.inv_freq()
    assert inv.shape == (32,) and inv.dtype == np.float32
    by_hand = {0: 1.0, 8: 0.03760603093086393, 9: 0.024955408670558694,
               13: 0.0027053709606281347, 18: 4.865409546207781e-06,
               19: 3.2286917967618477e-06, 31: 2.3545766813587272e-08}
    for j, want in by_hand.items():
        assert inv[j] == pytest.approx(want, rel=1e-6), j
    b = 500000.0
    assert inv[9] == pytest.approx(b ** (-18 / 64), rel=1e-6)
    assert inv[18] == pytest.approx(b ** (-36 / 64) / 128, rel=1e-6)
    assert rope.scale == 1.4852030263919618
    plain = M.Rope(128, 10000.0, None)
    assert plain.scale == 1.0
    np.testing.assert_allclose(
        plain.inv_freq(), 10000.0 ** (-np.arange(64) / 64.0), rtol=1e-6)


def test_rope_of_the_gpt_programs_is_bit_for_bit_what_it_was():
    """_rope gained the rotated width, a table and a scale; a call without
    them (every gpt() program's) computes exactly what it did."""
    from mxnet_tpu.serve.programs import _rope

    def parent(u, pos, base=10000.0):
        half = u.shape[-1] // 2
        inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        uf = u.astype(jnp.float32)
        u1, u2 = uf[..., :half], uf[..., half:]
        return jnp.concatenate([u1 * cos - u2 * sin,
                                u1 * sin + u2 * cos], -1).astype(u.dtype)

    u = jnp.asarray(np.random.default_rng(0).normal(size=(7, 3, 16)),
                    jnp.bfloat16)
    pos = jnp.arange(100, 107)
    assert (np.asarray(_rope(u, pos), np.float32)
            == np.asarray(parent(u, pos), np.float32)).all()
    # the first 8 of 16 dimensions turn, the rest pass through
    rope = M.Rope(8, 500000.0, (16.0, 32, 4.0, 1.0, 1.2))
    out = _rope(u.astype(jnp.float32), pos, inv=rope.inv_freq(), rot=8,
                scale=rope.scale)
    assert (np.asarray(out[..., 8:]) == np.asarray(u[..., 8:],
                                                   np.float32)).all()
    ang = 103.0 * rope.inv_freq()
    x = np.asarray(u[3, 1], np.float32)
    np.testing.assert_allclose(
        np.asarray(out[3, 1, :4]),
        1.2 * (x[:4] * np.cos(ang) - x[4:8] * np.sin(ang)), rtol=1e-5,
        atol=1e-6)


# -- the block manager's layer groups -----------------------------------------------

def test_a_window_request_holds_a_bounded_run_and_every_block_returns_once():
    W, bs = 20, 4
    per = math.ceil(W / bs) + 1
    wg = WindowGroup(num_blocks=1 + 2 * per + 9, block_size=bs, window=W,
                     scratch=9)
    assert wg.per_request == per and wg.capacity == 2
    wg.admit("a")
    returned = []
    free0 = sorted(wg._free)
    # a prompt of 50 in chunks of 32 and 18, then 60 decode steps
    pos = 0
    for span in (32, 18):
        wg.cover("a", pos, pos + span)
        assert len(wg.held("a")[1]) <= per + 9
        pos += span
        before = wg.held("a")[1]
        wg.trim("a", pos)
        returned += before[:len(before) - len(wg.held("a")[1])]
        assert len(wg.held("a")[1]) <= per
    for _ in range(60):
        wg.cover("a", pos, pos + 1)
        pos += 1
        before = wg.held("a")[1]
        wg.trim("a", pos)
        returned += before[:len(before) - len(wg.held("a")[1])]
        first, blocks = wg.held("a")
        assert len(blocks) <= per
        # what the next query sees is held
        assert first * bs <= max(pos - W + 1, 0)
        assert (first + len(blocks)) * bs >= pos
        table = wg.table("a", np.zeros(40, np.int32))
        assert (table[:first] == 0).all() and (table[first:first
                                                    + len(blocks)] > 0).all()
    assert wg.freed == len(returned)
    wg.release("a")
    assert sorted(wg._free) == free0                 # each exactly once
    assert len(set(returned)) <= len(free0) and wg.blocks_in_use == 0


def test_admission_refuses_when_either_group_is_full_and_free_frees_both():
    bs = 4
    wg = WindowGroup(num_blocks=1 + 2 * 3, block_size=bs, window=8)
    bm = BlockManager(64, bs, prefix_cache=False, window_group=wg)
    assert bm.prefix_off_reason and "window" in bm.prefix_off_reason
    bm.allocate("a", 9)
    bm.allocate("b", 9)
    assert not bm.can_allocate(9)                 # the window group is full
    with pytest.raises(NoFreeBlocks, match="window group"):
        bm.allocate("c", 9)
    assert "c" not in bm._tables                  # nothing half done
    bm.ensure_capacity("a", 10)                   # a decode step's position
    assert wg.blocks_in_use == 1 and bm.blocks_in_use == 6
    assert bm.utilization() == pytest.approx(7 / (63 + 6))
    assert bm.group_stats()["window"]["requests"] == 2
    bm.free("a")                                  # preemption, finish
    assert wg.blocks_in_use == 0 and bm.can_allocate(9)
    bm.allocate("c", 9)
    small = BlockManager(4, bs, prefix_cache=False,
                         window_group=WindowGroup(20, bs, 8))
    with pytest.raises(NoFreeBlocks):             # the global group is full
        small.allocate("x", 40)
    assert small.window.can_admit() and not small.window._blocks
    with pytest.raises(ValueError, match="prefix"):
        BlockManager(8, bs, prefix_cache=True,
                     window_group=WindowGroup(20, bs, 8))


def test_the_engine_keeps_a_window_request_inside_its_bound(model):
    """Between steps a request holds at most ceil(W / bs) + 1 = 3 blocks of
    the window group, whatever its context; the global group grows."""
    eng = _engine(model)
    req = eng.submit(_prompt(9, 37), max_new_tokens=20)
    seen = 0
    while eng.has_work():
        eng.step()
        if req.rid in eng.blocks.window._blocks:
            n = len(eng.blocks.window.held(req.rid)[1])
            assert n <= 3, n
            seen = max(seen, len(eng.blocks.table(req.rid)))
    assert seen >= (37 + 19) // BS
    eng.shutdown()


# -- what is refused, what is recorded ------------------------------------------------

@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_k=2, draft_params={}), "spec_k"),
    (dict(adapters=2), "adapters"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(quantize="int8"), "quantize"),
    (dict(tp=2), "tp"),
    (dict(host_kv_bytes=1 << 20), "host_kv_bytes"),
])
def test_what_a_routed_expert_engine_refuses(model, kw, word):
    with pytest.raises(ValueError, match=f"routed-expert decoder.*{word}"):
        _engine(model, **kw)


def test_block_export_and_import_are_refused(model):
    eng = _engine(model)
    assert eng.blocks.prefix_cache is False
    assert "window" in eng.statusz()["prefix_cache"]["disabled_reason"]
    with pytest.raises(ValueError, match="export"):
        eng.blocks.export_blocks(0, [1, 2, 3])
    with pytest.raises(ValueError, match="import"):
        eng.blocks.import_blocks([])
    eng.shutdown()


def test_spans_counters_and_statusz(model):
    mx.telemetry.enable()
    try:
        mx.telemetry.tracer().clear()
        eng = _engine(model)
        reqs = [eng.submit(_prompt(21 + i, 30 - 9 * i), max_new_tokens=14)
                for i in range(2)]
        eng.step()
        groups = eng.statusz()["kv_groups"]
        assert groups["window"]["window"] == WINDOW
        assert groups["window"]["per_request"] == 3
        assert groups["global"]["requests"] == 1
        eng.run()
        spans = mx.telemetry.tracer().spans(prefix="serve.")
        steps = [s[5] for s in spans if s[0] == "serve.step"]
        assert all({"blocks_global", "blocks_window",
                    "window_blocks_freed"} <= set(a) for a in steps)
        assert max(a["blocks_window"] for a in steps) <= 2 * 3 + 5
        freed = sum(a["window_blocks_freed"] for a in steps)
        assert freed == eng.statusz()["kv_groups"]["window"]["freed"] > 0
        passes = [s[5] for s in spans
                  if s[0] in ("serve.decode", "serve.prefill")]
        assert all(set(moe_ops.STATS) <= set(a) for a in passes)
        dec_passes = [s[5] for s in spans if s[0] == "serve.decode"]
        # 7 routed layers, 3 picks a row, 8 of 16 experts held
        for a in dec_passes:
            assert a["moe_picks"] == a["batch"] * 3 * 7
            assert 0 <= a["moe_picks_held"] <= a["moe_picks"]
            assert a["moe_experts_hit"] <= 8 * 7
            assert a["moe_load_max"] >= (a["moe_picks_held"] > 0)
        text = mx.telemetry.to_prometheus_text(mx.telemetry.registry())
        assert 'mxtpu_serve_kv_blocks_in_use{group="window"} 0' in text
        assert 'mxtpu_serve_kv_blocks_in_use{group="global"} 0' in text
        assert f"mxtpu_serve_kv_window_blocks_freed_total {freed}" in text
        held = sum(a["moe_picks_held"] for a in passes)
        assert f'mxtpu_serve_moe_picks_total{{held="yes"}} {held}' in text
        assert all(r.status == FINISHED for r in reqs)
        eng.shutdown()
    finally:
        mx.telemetry.disable()


def test_engines_key_on_their_description_and_group(model):
    """Two descriptions that differ in the experts held, and a hybrid
    engine, never share a compiled program; the fingerprint carries the
    description and the window group."""
    dec, params = model
    eng = _engine(model)
    other = _dec(held=(0, 8))
    eng2 = mx.serve.Engine(other.init_params(3, "float32"), symbol=other,
                           block_size=BS, num_blocks=64, max_batch=4,
                           max_model_len=64, prefill_chunk=16)
    assert eng._spec_key() != eng2._spec_key()
    fp = eng._aot_base_fp()
    assert fp["window_group"] == [6, 4 * 3 + 5 + 1]
    assert fp["cfg"]["hybrid"]["expert_offset"] == 4
    assert eng._spec_digest != eng2._spec_digest
    eng.shutdown()
    eng2.shutdown()
