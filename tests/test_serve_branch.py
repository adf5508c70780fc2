"""One-branch decoders (a layer is a mixer OR a feed-forward part: Mamba-2
in state groups, attention without positions, routed experts in a latent
width) through serve.Engine, at a small size on the CPU: two periods of
[mamba, moe, attention, moe], widths of tens, 2 state groups, 16 experts
of which 8 are held, 3 picks, blocks of 4.
``models/branch.py::reference_logits`` (float32, token-by-token
recurrence, every held expert by a one-hot product, no cache, no kernel)
is the yardstick throughout.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import branch as B
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.attention import paged_attention
from mxnet_tpu.ops.pallas_ssm_update import ssm_update_kernel
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.serve import hybrid as hybrid_mod
from mxnet_tpu.serve.scheduler import FINISHED

VOCAB, BS, CHUNK = 61, 4, 8
PATTERN = ["mamba", "moe", "attention", "moe"] * 2


def _dec(held=(4, 8), **kw):
    geo = dict(num_heads=4, kv_heads=2, head_dim=16, mamba_heads=4,
               mamba_head_dim=8, mamba_state=16, mamba_groups=2,
               mamba_chunk=CHUNK, num_experts=16, top_k=3, expert_ff=24,
               shared_ff=40, latent=16, routed_scale=2.5, experts_held=held)
    geo.update(kw)
    return B.branch_decoder(VOCAB, 32, PATTERN, **geo)


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
    (5, 6, 1),          # a whole prompt inside one scan chunk
    (16, 12, 1),        # a whole prompt of two chunks, then decode
    (37, 20, 3),        # three chunk passes: the state is carried
    (21, 30, 2),        # decode far past the prompt
])
def test_prefill_chunk_and_decode_equal_the_reference(model, plen, new,
                                                      passes):
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
    assert eng.statusz()["state_cache"]["in_use"] == 0
    eng.shutdown()


@pytest.mark.parametrize("kw", [
    dict(latent=40),                                 # a latent wider than an expert
    dict(held=(12, 4)),                              # the last share of four
    dict(mamba_groups=1),                            # one state group
    dict(mamba_groups=4, held=(0, 16)),              # a group a head; all held
], ids=["wide-latent", "last-share", "one-group", "four-groups-whole"])
def test_every_form_of_the_description_equals_the_reference(kw):
    """The latent's width, the share held and the state groups are the
    description's to choose: each through chunked prefill and decode
    gives the reference's logits."""
    kw = dict(kw)
    dec = _dec(held=kw.pop("held", (4, 8)), **kw)
    model = dec, dec.init_params(5, "float32")
    eng = _engine(model)
    prompt = _prompt(29, 29)
    req = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert req.prefill_passes == 2
    assert _regret(model, prompt, np.asarray(req.tokens)).max() <= 1e-4
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


def _skipped_total():
    text = mx.telemetry.to_prometheus_text(mx.telemetry.registry())
    m = re.search(r"^mxtpu_serve_state_updates_skipped_total (\S+)$", text,
                  re.M)
    return float(m.group(1)) if m else 0.0


def test_a_padded_decode_row_is_skipped_and_counted(model, monkeypatch):
    """Three requests at once in the 4-row bucket, so a decode pass of
    all three has one dead row: the grouped state kernel (interpreted, a
    group a head step) runs inside the engine's programs, every generated
    position is within 1e-4 of the reference's best, ``serve.decode``
    says the pass skipped one row, and the counter rises by the state
    layers for each such pass."""
    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    monkeypatch.setattr(ssm, "ssm_state_update",
                        lambda pool, layer, slots, *a: ssm_update_kernel(
                            pool, layer, slots, *a, heads_per_step=2,
                            interpret=True))
    mx.telemetry.enable()
    try:
        mx.telemetry.tracer().clear()
        before = _skipped_total()
        eng = _engine(model)
        prompts = [_prompt(70 + i, n) for i, n in enumerate((7, 12, 5))]
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        for prompt, req in zip(prompts, reqs):
            assert _regret(model, prompt, np.asarray(req.tokens)).max() <= 1e-4
        decodes = [s[5] for s in mx.telemetry.tracer().spans(
            prefix="serve.") if s[0] == "serve.decode"]
        three = [a for a in decodes if a["batch"] == 3]
        assert three and all(a["state_rows_skipped"] == 1 for a in three)
        assert sum(a["state_rows_skipped"] for a in decodes) == len(three)
        skipped = eng._state_ssm.shape[0] * len(three)
        assert _skipped_total() - before == skipped
        assert eng.statusz()["state_cache"]["updates_skipped"] == skipped
        eng.shutdown()
    finally:
        mx.telemetry.disable()


def test_what_the_engine_holds_and_says_of_a_one_branch_decoder(model):
    """The state pool is stacked over the state-space layers only, the
    K/V over the attention layers only and flat, the probe over the
    routed layers; ``statusz()["decoder"]`` says what the layers are; the
    router's counts ride back with every pass."""
    dec, _ = model
    eng = _engine(model)
    assert eng._state_ssm.shape == (2, 5, 4, 8, 16)
    assert eng._state_conv.shape == (2, 5, 3 * (32 + 2 * 2 * 16))
    assert eng._cache_k.shape == (2, 64, BS, 2 * 16)
    assert eng._probe.shape == (2, 4, 4, 2, 32)
    said = eng.statusz()["decoder"]
    assert said["mixers"] == {"mamba": 2, "attention": 2, "none": 4}
    assert said["ffns"] == {"moe": 4, "none": 4}
    assert said["state"]["groups"] == 2
    assert said["experts"] == {
        "router": "sigmoid", "picks": 3, "of": 16, "held": [4, 8],
        "act": "relu2", "latent": 16, "routed_scale": 2.5}
    req = eng.submit(_prompt(1, 9), max_new_tokens=4)
    eng.run()
    assert req.status == FINISHED
    probe = eng.routed_probe()
    assert probe["layers"] == (1, 3, 5, 7)
    assert np.abs(np.asarray(probe["decode"][1], np.float32)).sum() > 0
    eng.shutdown()


@pytest.mark.parametrize("arg,value", [
    ("prefix_cache", True), ("spec_k", 2), ("kv_dtype", "int8"),
    ("quantize", "int8"), ("tp", 2), ("host_kv_bytes", 1 << 20)])
def test_what_cannot_be_served_yet_is_refused_by_name(model, arg, value):
    with pytest.raises(ValueError, match=arg):
        _engine(model, **{arg: value})


def test_the_probe_rows_equal_the_reference_block(model):
    """What the decode program left in the probe: each routed block's
    output for its input, as the reference's block (shared expert plus
    this share's routed part, projected up from the latent) gives it."""
    dec, params = model
    eng = _engine(model)
    eng.submit(_prompt(2, 11), max_new_tokens=5)
    eng.run()
    probe = eng.routed_probe()
    P = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    u, y = (np.asarray(a, np.float32) for a in probe["decode"])
    for at, layer in enumerate(probe["layers"]):
        with jax.default_matmul_precision("highest"):
            want = (B.reference_shared(dec, P, layer, jnp.asarray(u[:1, at]))
                    + B.reference_routed(dec, P, layer,
                                         jnp.asarray(u[:1, at])))
        np.testing.assert_allclose(y[:1, at], np.asarray(want), atol=2e-5)
    eng.shutdown()


# -- the shares add up ------------------------------------------------------------

def test_the_shares_add_up_to_the_whole_block():
    """Four programs hold four experts each.  Their routed parts (each
    the latent partial sum projected up) plus the shared expert counted
    ONCE equal the uncut model's whole block, for every routed layer."""
    whole = _dec(held=(0, 16))
    params = whole.init_params(7, "float32")
    P = {k: jnp.asarray(v) for k, v in params.items()}
    u = jnp.asarray(np.random.default_rng(0).normal(size=(9, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        for layer in whole.moe_layers:
            p = f"{whole.name}_l{layer}"
            total = B.reference_shared(whole, P, layer, u)
            for off in range(0, 16, 4):
                share = _dec(held=(off, 4))
                Ps = dict(P)
                for name in ("experts_in", "experts_out"):
                    Ps[f"{p}_{name}_weight"] = \
                        P[f"{p}_{name}_weight"][off:off + 4]
                total = total + B.reference_routed(share, Ps, layer, u)
            want = (B.reference_shared(whole, P, layer, u)
                    + B.reference_routed(whole, P, layer, u))
            np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                       atol=1e-5)


def test_a_share_serves_its_part_of_the_whole_model():
    """An engine over a share's parameters (experts 4-11 sliced out of
    the whole model's) equals the reference given the same share."""
    whole = _dec(held=(0, 16))
    params = whole.init_params(9, "float32")
    share = _dec(held=(4, 8))
    mine = {k: (v[4:12] if "_experts_" in k else v)
            for k, v in params.items()}
    model = share, mine
    eng = _engine(model)
    prompt = _prompt(3, 19)
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert _regret(model, prompt, np.asarray(req.tokens)).max() <= 1e-4
    eng.shutdown()


# -- the router --------------------------------------------------------------------

def test_sigmoid_route_the_bias_moves_picks_and_never_a_weight():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 0.5, jnp.float32)
    idx0, w0 = moe_ops.route(logits, 3, "sigmoid")
    idx1, w1 = moe_ops.route(logits, 3, "sigmoid", bias)
    s = np.asarray(jax.nn.sigmoid(logits))
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()       # picks moved
    for idx, w, score in ((idx0, w0, s), (idx1, w1, s + np.asarray(bias))):
        idx, w = np.asarray(idx), np.asarray(w)
        # the picks are the k largest of the selection score
        want = np.argsort(-score, axis=-1)[:, :3]
        assert (np.sort(idx, -1) == np.sort(want, -1)).all()
        # the weights are the picks' OWN sigmoids over their sum: no bias
        own = np.take_along_axis(s, idx, -1)
        np.testing.assert_allclose(w, own / own.sum(-1, keepdims=True),
                                   rtol=1e-6)


def test_softmax_route_is_what_it_was_and_a_wrong_score_is_refused():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(8, 16)),
                         jnp.float32)
    idx, w = moe_ops.route(logits, 4)
    idx2, w2 = moe_ops.route(logits, 4, "softmax")
    assert (np.asarray(idx) == np.asarray(idx2)).all()
    assert (np.asarray(w) == np.asarray(w2)).all()
    with pytest.raises(ValueError, match="score"):
        moe_ops.route(logits, 4, "tanh")


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_routed_experts_of_each_form_equal_the_dense_sum(act):
    """22-way style routing at a small size: every pick of a held expert
    adds ``w * E(x)``, in the experts' own (latent) width."""
    rng = np.random.default_rng(2)
    T, L, F, E, count, off, k = 12, 16, 24, 16, 8, 4, 5
    gated = 2 if act == "swiglu" else 1
    x = jnp.asarray(rng.normal(size=(T, L)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(count, L, gated * F)) / 4, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(count, F, L)) / 5, jnp.float32)
    idx, w = moe_ops.route(jnp.asarray(rng.normal(size=(T, E)), jnp.float32),
                           k, "sigmoid")
    y, stats = moe_ops.routed_experts(x, w_in, w_out, idx, w, off, E, act=act)
    want = np.zeros((T, L), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j]) - off
            if 0 <= e < count:
                gu = np.asarray(x[t] @ w_in[e])
                hid = (np.square(np.maximum(gu, 0)) if act == "relu2" else
                       gu[:F] / (1 + np.exp(-gu[:F])) * gu[F:])
                want[t] += float(w[t, j]) * (hid @ np.asarray(w_out[e]))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert int(stats[0]) == T * k
    with pytest.raises(ValueError, match="act"):
        moe_ops.routed_experts(x, w_in, w_out, idx, w, off, E, act="gelu")


@pytest.mark.parametrize("n,cap,want", [
    (1024, 1024, 1024), (3072, 1024, 1024), (2048, 1024, 1024),
    (2688, 1024, 896), (640, 1024, 640), (1000 * 3, 1024, 1024)])
def test_grouped_matmul_tiles_divide_an_axis_where_lane_groups_can(n, cap,
                                                                   want):
    assert moe_ops._tile(n, cap) == want


# -- the recurrence in groups --------------------------------------------------------

def _recurrence(x, dt, dA, Bm, Cm, D, S):
    """Token by token; Bm, Cm (T, G, N), head h reads group h // (H / G)."""
    R = x.shape[1] // Bm.shape[1]
    ys = []
    for t in range(x.shape[0]):
        Bh, Ch = np.repeat(Bm[t], R, 0), np.repeat(Cm[t], R, 0)   # (H, N)
        S = (np.exp(dA[t])[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
        ys.append((S * Ch[:, None, :]).sum(-1) + D[:, None] * x[t])
    return np.stack(ys), S


def _scan_inputs(rng, T, H, P, N, G):
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, size=(T, H)).astype(np.float32)
    dA = (-rng.uniform(0.5, 4.0, size=H) * dt).astype(np.float32)
    Bm = rng.normal(size=(T, G, N)).astype(np.float32)
    Cm = rng.normal(size=(T, G, N)).astype(np.float32)
    D = rng.normal(size=H).astype(np.float32)
    S0 = rng.normal(size=(H, P, N)).astype(np.float32)
    return x, dt, dA, Bm, Cm, D, S0


@pytest.mark.parametrize("T,G", [(CHUNK, 2), (CHUNK - 1, 2), (3 * CHUNK, 4),
                                 (CHUNK + 1, 4), (1, 2), (2 * CHUNK, 1)])
def test_grouped_scan_equals_the_recurrence(T, G):
    x, dt, dA, Bm, Cm, D, S0 = _scan_inputs(np.random.default_rng(T + G),
                                            T, 4, 8, 16, G)
    y, S = ssm.ssd_chunked_scan(*map(jnp.asarray, (x, dt, dA, Bm, Cm, D, S0)),
                                CHUNK)
    want_y, want_S = _recurrence(x, dt, dA, Bm, Cm, D, S0)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=2e-5)


def test_grouped_scan_carried_across_passes_equals_one_pass():
    """A span cut into passes of 11, 8 and 5 positions, each starting from
    the state the last one left, against the recurrence over all 24."""
    x, dt, dA, Bm, Cm, D, S0 = _scan_inputs(np.random.default_rng(5),
                                            24, 4, 8, 16, 2)
    S, ys, at = jnp.asarray(S0), [], 0
    for n in (11, 8, 5):
        cut = [jnp.asarray(a[at:at + n]) for a in (x, dt, dA, Bm, Cm)]
        y, S = ssm.ssd_chunked_scan(*cut, jnp.asarray(D), S, CHUNK)
        ys.append(np.asarray(y))
        at += n
    want_y, want_S = _recurrence(x, dt, dA, Bm, Cm, D, S0)
    np.testing.assert_allclose(np.concatenate(ys), want_y, atol=3e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=3e-5)


def _update_inputs(rng, Bn, H, P, N, G, slots=6, layers=2):
    pool = rng.normal(size=(layers, slots, H, P, N)).astype(np.float32)
    x = rng.normal(size=(Bn, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, size=(Bn, H)).astype(np.float32)
    dA = (-rng.uniform(0.5, 4.0, size=H) * dt).astype(np.float32)
    Bm = rng.normal(size=(Bn, G, N)).astype(np.float32)
    Cm = rng.normal(size=(Bn, G, N)).astype(np.float32)
    D = rng.normal(size=H).astype(np.float32)
    return pool, x, dt, dA, Bm, Cm, D


@pytest.mark.parametrize("H,G,hb,slots", [
    (4, 2, None, [4, 1, 2]),        # the XLA form
    (4, 2, 4, [4, 1, 2]),           # a step takes both groups
    (4, 2, 2, [4, 1, 2]),           # a step takes one whole group
    (8, 2, 2, [4, 1, 2]),           # a group spans two steps
    (8, 4, 4, [4, 1, 2]),           # two groups a step, two steps a row
    (4, 1, 2, [4, 1, 2]),           # one group, two steps
    # dead rows (slot 0): trailing, in front of and between live rows,
    # and one live row, at several groups a step and a group over steps
    (4, 2, 4, [4, 1, 0, 0]),
    (8, 2, 2, [0, 4, 0, 1, 0]),
    (8, 4, 4, [0, 2, 0, 5]),
    (8, 2, 2, [0, 0, 3, 0]),
], ids=["jnp", "groups-in-a-step", "a-group-a-step", "group-over-steps",
        "two-groups-two-steps", "one-group", "groups-in-a-step-dead-behind",
        "group-over-steps-dead-between", "two-groups-two-steps-dead-between",
        "group-over-steps-one-live"])
def test_grouped_state_update_equals_one_step_of_the_recurrence(H, G, hb,
                                                                slots):
    """``hb`` None: ``ssm_state_update``'s XLA form; else the kernel in
    interpret mode with ``hb`` heads a grid step, with dead rows in TPU
    interpret mode (a block is copied in or out only when its index
    changes, as on the chip; a never-written buffer holds NaN).  A dead
    row's ``y`` is exactly 0 and no slot a live row does not name moves,
    slot 0 included."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(H * 10 + G)
    pool, x, dt, dA, Bm, Cm, D = _update_inputs(rng, len(slots), H, 8, 16, G)
    slots = np.array(slots, np.int32)
    args = [jnp.asarray(a) for a in (x, dt, dA, Bm, Cm, D)]
    if hb is None:
        y, new = ssm.ssm_state_update(jnp.asarray(pool), 1, jnp.asarray(slots),
                                      *args, impl="jnp")
    else:
        mode = (pltpu.InterpretParams(uninitialized_memory="nan")
                if (slots == 0).any() else None)
        with pltpu.force_tpu_interpret_mode(mode):
            y, new = ssm_update_kernel(jnp.asarray(pool), 1,
                                       jnp.asarray(slots), *args,
                                       heads_per_step=hb, interpret=True)
    y, new = np.asarray(y), np.asarray(new)
    for b, slot in enumerate(slots):
        if not slot:
            assert (y[b] == 0).all()
            continue
        want_y, want_S = _recurrence(x[b:b + 1], dt[b:b + 1], dA[b:b + 1],
                                     Bm[b:b + 1], Cm[b:b + 1], D,
                                     pool[1, slot])
        np.testing.assert_allclose(y[b], want_y[0], atol=2e-5)
        np.testing.assert_allclose(new[1, slot], want_S, atol=2e-5)
    untouched = [s for s in range(pool.shape[1]) if s not in slots[slots > 0]]
    assert (new[1, untouched] == pool[1, untouched]).all()
    assert (new[0] == pool[0]).all()


def test_a_step_that_cuts_across_groups_is_refused():
    rng = np.random.default_rng(0)
    pool, x, dt, dA, Bm, Cm, D = _update_inputs(rng, 2, 12, 8, 16, 3)
    with pytest.raises(ValueError, match="groups"):
        ssm_update_kernel(jnp.asarray(pool), 0, jnp.zeros(2, jnp.int32),
                          *map(jnp.asarray, (x, dt, dA, Bm, Cm, D)),
                          heads_per_step=6, interpret=True)


@pytest.mark.parametrize("what", ["scan", "update-jnp", "update-kernel"])
def test_one_group_in_either_form_is_bit_for_bit_what_it_was(what):
    """``(rows, N)`` is the form the one-group descriptions pass, and the
    code under it is what it was; ``(rows, 1, N)`` gives the same bits."""
    rng = np.random.default_rng(11)
    if what == "scan":
        x, dt, dA, Bm, Cm, D, S0 = _scan_inputs(rng, 19, 4, 8, 16, 1)
        run = lambda b, c: ssm.ssd_chunked_scan(
            *map(jnp.asarray, (x, dt, dA, b, c, D, S0)), CHUNK)
    else:
        pool, x, dt, dA, Bm, Cm, D = _update_inputs(rng, 3, 4, 8, 16, 1)
        slots = jnp.asarray([3, 1, 5], jnp.int32)
        if what == "update-jnp":
            run = lambda b, c: ssm.ssm_state_update(
                jnp.asarray(pool), 0, slots,
                *map(jnp.asarray, (x, dt, dA, b, c, D)), impl="jnp")
        else:
            run = lambda b, c: ssm_update_kernel(
                jnp.asarray(pool), 0, slots,
                *map(jnp.asarray, (x, dt, dA, b, c, D)), interpret=True)
    flat = run(Bm[:, 0], Cm[:, 0])
    grouped = run(Bm, Cm)
    for a, b in zip(flat, grouped):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_the_gated_norm_is_over_each_groups_channels(model):
    """``_mamba_out`` norms each state group's channels on their own:
    scaling one group's channels leaves the other group's output rows of
    the norm unchanged (a norm over all of d_inner would move them)."""
    dec, params = model
    p = f"{dec.name}_l0"
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(3, dec.d_inner)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(3, dec.d_inner)), jnp.float32)
    eye = dict(params)
    eye[f"{p}_out_proj_weight"] = jnp.eye(dec.d_inner, dtype=jnp.float32)
    half = dec.d_inner // 2
    a = np.asarray(hybrid_mod._mamba_out(dec, eye, p, y, z))
    b = np.asarray(hybrid_mod._mamba_out(
        dec, eye, p, y.at[:, :half].multiply(7.0), z))
    np.testing.assert_allclose(a[:, half:], b[:, half:], atol=1e-6)
    # its own rows: an RMS norm undoes the scale (up to eps)
    np.testing.assert_allclose(a[:, :half], b[:, :half], rtol=1e-3)


# -- attention: few heads of 128 in a flat cache -----------------------------------

def test_flat_cache_of_heads_of_128_through_the_packed_kernel():
    """2 kv heads of 128 under 32 query heads (a GQA ratio of 16): the
    packed kernel over the flat cache (a lane group a kv head; interpret
    mode) against the XLA form."""
    rng = np.random.default_rng(0)
    Bn, Hq, Hkv, Dh, nb, bs, W = 3, 32, 2, 128, 40, 16, 8
    q = jnp.asarray(rng.normal(size=(Bn, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, nb, bs, Hkv * Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, nb, bs, Hkv * Dh)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:Bn * W]
                         .reshape(Bn, W), jnp.int32)
    ctx = jnp.asarray([100, 1, 37], jnp.int32)
    got = paged_attention(q, k, v, tables, ctx, layer=0, flat_heads=Hkv,
                          impl="pallas")
    want = paged_attention(q, k, v, tables, ctx, layer=0, flat_heads=Hkv,
                           impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_matmul_flops_count_a_layers_one_branch(model):
    """The operations of a pass: every matrix once (a held expert at the
    share of the picks a uniform router gives it), no feed-forward part
    under a mixer and none the other way round."""
    dec, _ = model
    per = 0
    for name, shape in dec.param_shapes().items():
        if name.endswith("_weight") and "tok_embed" not in name \
                and "conv" not in name and not name.endswith("head_weight"):
            n = 2 * int(np.prod(shape))
            per += n * dec.top_k / dec.num_experts if "_experts_" in name \
                else n
    assert hybrid_mod.matmul_flops(dec, 10, 2) == int(10 * per) \
        + 2 * 2 * VOCAB * 32
