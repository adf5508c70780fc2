"""Block-diffusion decoders served through ``mx.serve.Engine``
(``models/moe.py`` with ``diffusion=(block, steps, mask_id)``;
``serve/hybrid.py``'s denoise pass, ``serve/engine.py``'s
``_run_denoise`` / ``_read_denoise``).

What is held here, on the CPU at tiny sizes and seeded random weights:
the block-causal span attention (dense form and the Mosaic kernel in the
interpreter) and the paged kernel with a row's block positions inside
each kv head's group, against a plain reference; the engine's denoise
and store passes against ``reference_generate`` at every step (logits
within a stated tolerance, tokens and unmask order equal in float32);
committed K/V against the clean block's; the step loop at depth 1
against depth 0; what the engine refuses.  Counts and values only."""

import collections
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import moe as M
from mxnet_tpu.ops import attention as attn_ops
from mxnet_tpu.ops.pallas_paged_attention import paged_attention_kernel
from mxnet_tpu.ops.pallas_span_attention import span_attention_kernel
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.serve import hybrid as hybrid_mod

VOCAB, MASK, BLOCK, STEPS = 61, 60, 4, 4
# float32 engine against the float32 reference: the logits of a denoise
# step agree to this (the same products in another order: 1e-5 relative
# seen; a wrong mask, position or block moves them by O(1))
LOGIT_TOL = 2e-4


def _dec(**kw):
    args = dict(gate=False, qk_norm=True, diffusion=(BLOCK, STEPS, MASK),
                rope={M.FULL: M.Rope(16, 1e6, None)})
    args.update(kw)
    return M.moe_decoder(VOCAB, 32, [M.FULL] * 2, heads=[4, 4], kv_heads=2,
                         head_dim=16, window=0, ffn_types=["moe"] * 2,
                         d_ff=0, num_experts=8, top_k=2, expert_ff=24,
                         shared_ff=0, **args)


def _params(dec, seed=3):
    """Seeded weights with a peaked head (logits of spread 4), so that a
    step's most confident position is decided by more than rounding."""
    P = dict(dec.init_params(seed))
    P[f"{dec.name}_head_weight"] = P[f"{dec.name}_head_weight"] * 4.0
    return P


@pytest.fixture(scope="module")
def model():
    dec = _dec()
    return dec, _params(dec)


GEO = dict(block_size=4, num_blocks=96, max_batch=4, max_model_len=64,
           prefill_chunk=8)


def _engine(model, **kw):
    dec, params = model
    return mx.serve.Engine(params, symbol=dec, **dict(GEO, **kw))


def _prompts(lens, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, MASK, (n,)).astype(np.int32) for n in lens]


# -- the description and the reference -------------------------------------------

def test_transfer_counts_split_a_block_over_its_steps():
    assert M.transfer_counts(4, 4) == [1, 1, 1, 1]
    assert M.transfer_counts(2, 4) == [1, 1, 0, 0]
    assert M.transfer_counts(7, 3) == [3, 2, 2]
    assert M.transfer_counts(0, 4) == [0, 0, 0, 0]


def test_description_carries_what_the_configuration_sets(model):
    dec, params = model
    assert dec.diffusion and (dec.block, dec.steps, dec.mask_id) == (4, 4, 60)
    names = set(dec.param_shapes())
    assert "moe_l0_q_norm_gamma" in names and "moe_l0_k_norm_gamma" in names
    assert not any("gate_weight" in n or "shared" in n for n in names)
    with pytest.raises(ValueError, match="window layers"):
        M.moe_decoder(VOCAB, 32, [M.WINDOW] * 2, [4, 4], 2, 16, 8,
                      ["moe"] * 2, 0, 8, 2, 24, 0,
                      diffusion=(BLOCK, STEPS, MASK))
    with pytest.raises(ValueError, match="block of 2 or more"):
        _dec(diffusion=(1, 4, MASK))


def test_reference_mask_is_block_causal(model):
    """A position's logits do not move when a LATER block changes, and do
    when a later position of its own block does."""
    dec, params = model
    toks = np.arange(1, 13) % MASK
    base = np.asarray(dec.reference_logits(params, toks))
    later = toks.copy()
    later[8:] = (later[8:] + 7) % MASK             # the third block
    np.testing.assert_allclose(
        np.asarray(dec.reference_logits(params, later))[:8], base[:8],
        rtol=0, atol=1e-5)
    inside = toks.copy()
    inside[7] = (inside[7] + 7) % MASK             # the second block's last
    moved = np.asarray(dec.reference_logits(params, inside))
    assert np.abs(moved[4] - base[4]).max() > 1e-3
    np.testing.assert_allclose(moved[:4], base[:4], rtol=0, atol=1e-5)
    causal = np.asarray(dec.reference_logits(params, inside, block=1))
    np.testing.assert_allclose(causal[4], np.asarray(
        dec.reference_logits(params, toks, block=1))[4], rtol=0, atol=1e-5)


# -- the block-causal span and the paged block ------------------------------------

def _span_reference(q, k, v, start, block, scale):
    """Plain numpy: row t at position start + t sees keys j < (pos //
    block + 1) * block."""
    T, Hq, Dh = q.shape
    S, Hkv, _ = k.shape
    g = Hq // Hkv
    out = np.zeros((T, Hq, Dh), np.float64)
    for t in range(T):
        end = min(((start + t) // block + 1) * block, S)
        for h in range(Hq):
            s = k[:end, h // g] @ q[t, h] * scale
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ v[:end, h // g]
    return out


@pytest.mark.parametrize("block,start", [(1, 0), (1, 37), (4, 0), (4, 64),
                                         (4, 37), (4, 50)])
def test_block_causal_span_attention(block, start):
    rng = np.random.RandomState(block * 100 + start)
    T, S, Hq, Hkv, Dh = 32, 256, 4, 2, 128
    q, k, v = (rng.randn(*s).astype(np.float32) for s in
               ((T, Hq, Dh), (S, Hkv, Dh), (S, Hkv, Dh)))
    scale = np.float32(Dh ** -0.5)
    want = _span_reference(q, k, v, start, block, scale)
    dense = attn_ops.masked_attention(q, k, v, start, scale, block=block)
    np.testing.assert_allclose(np.asarray(dense).reshape(T, Hq, Dh), want,
                               rtol=2e-4, atol=2e-5)
    kern = span_attention_kernel(q, k, v, start, T, scale, interpret=True,
                                 block_q=16, block_k=128, resident=128,
                                 block=block)
    np.testing.assert_allclose(np.asarray(kern).reshape(T, Hq, Dh), want,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block", [1, 4])
def test_span_kv_tiles_mirror_the_block_walk(block):
    """The host's count of visited tiles is the count of (query tile, key
    tile) pairs holding a key some real row sees."""
    T, S, bq, bk = 1024, 2048, attn_ops.SPAN_BLOCK_Q, attn_ops.SPAN_BLOCK_K
    for start, n in ((0, 1024), (508, 1024), (512, 700), (1020, 1000)):
        n = min(n, S - start, T)
        seen = set()
        for t in range(n):
            pos = start + t
            end = min((pos // block + 1) * block, S)
            for j in range(0, end, bk):
                seen.add(((pos - start) // bq, j // bk))
        visited, _ = attn_ops.span_kv_tiles(T, S, start, n, block=block)
        assert visited == len(seen), (start, n)


def test_paged_kernel_takes_a_block_a_row():
    """The paged kernel as it stands, told B * heads query heads: a row's
    B positions inside each kv head's group, one context a row."""
    rng = np.random.RandomState(0)
    R, B, Hkv, G, Dh, bs, W = 3, 4, 2, 2, 128, 16, 4
    nb = R * W + 1
    kc = rng.randn(1, nb, bs, Hkv, Dh).astype(np.float32)
    vc = rng.randn(1, nb, bs, Hkv, Dh).astype(np.float32)
    tables = (1 + np.arange(R * W)).reshape(R, W).astype(np.int32)
    ctx = np.array([8, 36, 64], np.int32)            # each a block's end
    q = rng.randn(R, B, Hkv, G, Dh).astype(np.float32)
    qg = q.transpose(0, 2, 1, 3, 4).reshape(R, Hkv * B * G, Dh)
    scale = np.float32(Dh ** -0.5)
    kern = paged_attention_kernel(jnp.asarray(qg), kc, vc, tables, ctx,
                                  scale=scale, interpret=True, layer=0)
    dense = attn_ops.paged_attention(jnp.asarray(qg), kc, vc, tables, ctx,
                                     scale=scale, impl="jnp", layer=0)
    for out in (kern, dense):
        got = np.asarray(out).reshape(R, Hkv, B, G, Dh)
        for r in range(R):
            keys = kc[0, tables[r]].reshape(-1, Hkv, Dh)[:ctx[r]]
            vals = vc[0, tables[r]].reshape(-1, Hkv, Dh)[:ctx[r]]
            for b in range(B):
                want = _span_reference(
                    q[r, b].reshape(1, Hkv * G, Dh), keys, vals,
                    ctx[r] - B + b, B, scale)[0].reshape(Hkv, G, Dh)
                np.testing.assert_allclose(got[r, :, b], want, rtol=2e-4,
                                           atol=2e-5)


# -- the unmasking step --------------------------------------------------------

UR, UB, UV, UMASK = 8, 4, 1021, 700


def _unmask_as_before(mask_id, toks, logits, counts):
    """The reference unmasking step: the logits upcast whole to float32
    and relaid out to ``(R, B, V)`` before every reduction.  Returns
    ``(block, unmasked, conf (R, B))``."""
    R, B = toks.shape
    lg = logits.astype(jnp.float32).reshape(R, B, -1)
    lg = jnp.where(jnp.arange(lg.shape[-1]) == mask_id,
                   np.float32(-np.inf), lg)
    cand = jnp.argmax(lg, axis=-1).astype(toks.dtype)
    top = jnp.max(lg, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
    masked = toks == mask_id
    score = jnp.where(masked, conf, np.float32(-1.0))
    at = jnp.arange(B)
    ahead = jnp.logical_or(
        score[:, None, :] > score[:, :, None],
        jnp.logical_and(score[:, None, :] == score[:, :, None],
                        (at[None, :] < at[:, None])[None]))
    rank = jnp.sum(ahead, axis=-1)
    fresh = jnp.logical_and(masked, rank < counts[:, None])
    return jnp.where(fresh, cand, toks), fresh, conf


def _unmask_inputs(case, dtype, seed=11):
    """Blocks, logits ``(R * B, V)`` of spread 4 and counts 0 .. B over
    the rows; row 7 has no masked position left, rows 0-3 are all mask.
    ``case`` plants: the mask id on top of every position of a row, an
    exact tie between two tokens of one position, or two masked
    positions of one row with the same logits (so the same
    confidence)."""
    rng = np.random.RandomState(seed)
    lg = (rng.randn(UR * UB, UV) * 4.0).astype(np.float32)
    toks = rng.randint(0, UMASK, size=(UR, UB)).astype(np.int32)
    toks[:4] = UMASK
    toks[4:7, 1:] = UMASK
    counts = (np.arange(UR) % (UB + 1)).astype(np.int32)
    if case == "mask_on_top":
        lg[UB:2 * UB, UMASK] = 40.0
    elif case == "token_tie":
        for p in range(UR * UB):
            lg[p, [17, 900]] = 24.0             # exact in bfloat16 too
    elif case == "conf_tie":
        for r in range(UR):
            lg[r * UB + 3] = lg[r * UB + 1]
    return (jnp.asarray(toks), jnp.asarray(lg).astype(dtype),
            jnp.asarray(counts))


@pytest.mark.parametrize("case", ["plain", "mask_on_top", "token_tie",
                                  "conf_tie"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_unmask_equals_the_relaid_out_float32_form(case, dtype):
    """Reducing the head's rows where they are gives the tokens and
    unmasked positions of the upcast ``(R, B, V)`` form, and its
    confidences to the last ulps of the sum's order."""
    dec = types.SimpleNamespace(mask_id=UMASK)
    toks, lg, counts = _unmask_inputs(case, dtype)
    want_block, want_fresh, want_conf = _unmask_as_before(
        UMASK, toks, lg, counts)
    block, fresh = jax.jit(lambda t, l, c: hybrid_mod.unmask(dec, t, l, c))(
        toks, lg, counts)
    cand, conf = hybrid_mod._confident(dec, lg)
    np.testing.assert_array_equal(np.asarray(block), np.asarray(want_block))
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(want_fresh))
    np.testing.assert_allclose(np.asarray(conf).reshape(UR, UB),
                               np.asarray(want_conf), rtol=1e-6, atol=0)
    assert np.asarray(cand).max() < UV and not (np.asarray(cand)
                                                == UMASK).any()
    # what the planted cases must show, so none of them passes vacuously
    fresh = np.asarray(fresh)
    assert fresh.sum(-1).tolist() == [0, 1, 2, 3, 3, 0, 1, 0]
    if case == "mask_on_top":
        assert (np.asarray(lg[UB:2 * UB]).argmax(-1) == UMASK).all()
    if case == "token_tie":
        assert (np.asarray(cand) == 17).all()
    if case == "conf_tie":
        conf = np.asarray(conf).reshape(UR, UB)
        assert (conf[:, 3] == conf[:, 1]).all()
        # positions 1 and 3 tie: 3 never goes before 1
        assert not (fresh[:, 3] & ~fresh[:, 1]).any()


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_unmask_reduces_the_logits_where_the_head_wrote_them():
    """No V-wide reshape; every reduction over the vocabulary takes the
    2-D ``(R * B, V)`` logits, the max and argmax in their own dtype.
    (XLA fuses the float32 conversion that feeds the sum into the sum:
    whether a copy is stored is read off the chip's trace.)"""
    dec = types.SimpleNamespace(mask_id=UMASK)
    toks, lg, counts = _unmask_inputs("plain", jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda t, l, c: hybrid_mod.unmask(dec, t, l, c))(
        toks, lg, counts).jaxpr
    seen = collections.Counter()
    for e in _eqns(jaxpr):
        ins = [v.aval for v in e.invars if hasattr(v.aval, "shape")]
        wide = [a for a in ins if UV in a.shape]
        name = e.primitive.name
        assert not (name == "reshape" and wide), e
        if name in ("argmax", "reduce_max", "reduce_sum") and wide:
            (a,) = wide
            assert a.shape == (UR * UB, UV), e
            if name != "reduce_sum":
                assert a.dtype == jnp.bfloat16, e
            seen[name] += 1
    assert seen == {"argmax": 1, "reduce_max": 1, "reduce_sum": 1}


# -- the engine against the reference ------------------------------------------------

def _logit_taps(monkeypatch):
    """Every decode program's logits as it computed them: ``_logits``
    wrapped with a host callback, in fresh programs."""
    seen = []
    orig = hybrid_mod._logits

    def tapped(dec, params, x):
        lg = orig(dec, params, x)
        if x.shape[0] % BLOCK == 0 and x.shape[0] > 1:
            jax.debug.callback(lambda a: seen.append(np.asarray(a)), lg)
        return lg

    monkeypatch.setattr(hybrid_mod, "_logits", tapped)
    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    return seen


def test_denoise_and_store_passes_follow_the_reference(model, monkeypatch):
    """One request at a time, prompts with P mod B = 0..3 and outputs
    that end inside a block: every denoise pass's logits within
    ``LOGIT_TOL`` of the reference's for the same block, the tokens and
    the order of unmasking equal."""
    dec, params = model
    seen = _logit_taps(monkeypatch)
    for plen, new in ((12, 5), (9, 7), (14, 10), (11, 3), (23, 6)):
        eng = _engine(model, max_batch=1)
        prompt = _prompts([plen], seed=plen)[0]
        seen.clear()
        req = eng.submit(prompt, max_new_tokens=new)
        eng.run()
        ref = dec.reference_generate(params, prompt, new)
        assert req.status == "finished"
        assert req.tokens == ref["tokens"]
        assert req.unmasked == ref["unmasked"]
        # decode programs: the denoise passes, then each block's store
        denoise = [lg for lg in seen if lg.shape[0] == BLOCK]
        blocks = len({start for start, _, _, _ in ref["steps"]})
        assert len(denoise) == len(ref["steps"]) + blocks
        it = iter(denoise)
        last = None
        for start, went_in, lg_ref, _ in ref["steps"]:
            if last is not None and start != last:
                next(it)                    # the store pass of the block
            last = start
            np.testing.assert_allclose(next(it), lg_ref, rtol=0,
                                       atol=LOGIT_TOL)
        assert req.cache_len == (plen + new + BLOCK - 1) // BLOCK * BLOCK
        eng.shutdown()


def test_a_batch_with_chunked_prompts_follows_the_reference(model):
    """Rows at different steps of their blocks in one pass, prompts
    chunked beside decoding rows (a chunk of whole blocks), more requests
    than slots: each request's tokens and unmask order are the
    reference's."""
    dec, params = model
    eng = _engine(model)
    lens, news = (9, 12, 14, 4, 23, 31, 6), (7, 5, 10, 3, 6, 9, 13)
    prompts = _prompts(lens)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    eng.run()
    for req, p, n in zip(reqs, prompts, news):
        ref = dec.reference_generate(params, p, n)
        assert req.tokens == ref["tokens"], req.rid
        assert req.unmasked == ref["unmasked"], req.rid
    st = eng.statusz()
    assert st["decoder"]["diffusion"] == {
        "block": 4, "steps": 4, "mask_id": 60,
        "remasking": "low_confidence_static"}
    eng.shutdown()


def test_a_preempted_request_resumes_to_the_reference(model):
    """A cache too small for every row's blocks preempts running
    requests, some inside a block: each resumes by re-prefilling its
    committed blocks, reopens its block from the start, and its tokens
    and unmask record are still the reference's."""
    dec, params = model
    eng = _engine(model, num_blocks=14)
    inside = []                  # positions unmasked in the dropped block
    preempt = eng.scheduler.preempt

    def noting(req):
        inside.append(sum(u[0] >= req.cache_len for u in req.unmasked))
        return preempt(req)

    eng.scheduler.preempt = noting
    lens, news = (9, 13, 10, 11), (20, 18, 22, 17)
    prompts = _prompts(lens, seed=7)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    eng.run()
    assert eng.stats().preemptions == len(inside) > 0
    assert max(inside) > 0
    for req, p, n in zip(reqs, prompts, news):
        ref = dec.reference_generate(params, p, n)
        assert req.status == "finished", req.rid
        assert req.tokens == ref["tokens"], req.rid
        assert req.unmasked == ref["unmasked"], req.rid
    eng.shutdown()


def test_committed_kv_is_the_clean_blocks(model):
    """What the store passes committed equals what a prefill of the
    committed sequence writes (the same K/V at every layer): the cache
    holds the clean blocks, not a denoise pass's masked ones."""
    dec, params = model
    eng = _engine(model)
    prompt = _prompts([10])[0]
    req = eng.submit(prompt, max_new_tokens=20)
    while req.cache_len < 20:
        eng.step()
        eng.stats()                      # reads the unread pass
    n = req.cache_len
    assert n % BLOCK == 0
    table = np.asarray(eng.blocks.table(req.rid))
    rows = [np.asarray(c)[:, table].reshape(2, -1, 2, 16)[:, :n]
            for c in (eng._cache_k, eng._cache_v)]
    got = dict((p, t) for p, t, _ in req.unmasked)
    seq = list(prompt[:8]) + [int(got.get(i, prompt[i] if i < 10 else -1))
                              for i in range(8, n)]
    assert -1 not in seq
    other = _engine(model)
    twin = other.submit(np.asarray(seq, np.int32), max_new_tokens=1)
    while twin.cache_len < n:
        other.step()
        other.stats()
    t2 = np.asarray(other.blocks.table(twin.rid))
    for a, c in zip(rows, (other._cache_k, other._cache_v)):
        b = np.asarray(c)[:, t2].reshape(2, -1, 2, 16)[:, :n]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    eng.shutdown()
    other.shutdown()


def test_run_ahead_keeps_depth_one(model):
    """The step loop enqueues a denoise pass behind the unread one (the
    host foresees every row's kind and position; its block is taken from
    the token pool on the device), and gives what the same loop at depth
    0 gives."""
    dec, params = model
    out = []
    for hold in (False, True):
        eng = _engine(model)
        if hold:
            launch = eng._launch
            eng._launch = (lambda behind, launch=launch:
                           None if behind is not None else launch(None))
        prompts = _prompts((9, 13, 22))
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (9, 6, 11))]
        eng.run()
        order = eng.statusz()["step_order"]
        out.append(([r.tokens for r in reqs], [r.unmasked for r in reqs],
                    order))
        eng.shutdown()
    (tok1, rec1, order1), (tok0, rec0, order0) = out
    assert tok1 == tok0 and rec1 == rec0
    assert order1["ahead"] > 3 * sum(order1["settled"].values())
    assert order0["ahead"] == 0


def test_telemetry_counts_passes_blocks_and_unmasked(model):
    """``serve.decode`` carries the rows at each block step, what the pass
    unmasked and committed and the positions its rows attend over; the
    three counters add them up."""
    dec, params = model
    mx.telemetry.reset()
    mx.telemetry.enable()
    try:
        mx.telemetry.tracer().clear()
        eng = _engine(model)
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(_prompts((9, 16)), (7, 8))]
        eng.run()
        args = [s[5] for s in mx.telemetry.tracer().spans(prefix="serve.")
                if s[0] == "serve.decode"]
        assert args and all({"block_step", "unmasked", "committed",
                             "contexts"} <= set(a) for a in args)
        unmasked = sum(len(r.unmasked) for r in reqs)
        committed = sum(r.cache_len - (r.prompt.size - r.prompt.size % 4)
                        for r in reqs) // BLOCK
        assert sum(a["unmasked"] for a in args) == unmasked
        assert sum(a["committed"] for a in args) == committed
        at = np.array([[int(n) for n in a["block_step"].split(",")]
                       for a in args])
        assert at.shape[1] == STEPS + 1
        assert at.sum() == sum(a["batch"] for a in args)
        assert at[:, STEPS].sum() == committed
        text = mx.telemetry.to_prometheus_text(mx.telemetry.registry())
        assert f"mxtpu_serve_blocks_committed_total {committed}" in text
        assert f"mxtpu_serve_tokens_unmasked_total {unmasked}" in text
        assert (f'mxtpu_serve_block_passes_total{{kind="store"}} '
                f"{committed}") in text
        assert (f'mxtpu_serve_block_passes_total{{kind="denoise"}} '
                f"{at[:, :STEPS].sum()}") in text
        # admission to the first tokens: one span a request, ending at
        # the read of its first block's store pass
        first = {s[5]["rid"]: s for s in mx.telemetry.tracer().spans(
            prefix="serve.request.prefill")}
        assert set(first) == {r.rid for r in reqs}
        assert all(first[r.rid][4] == r.first_token_t for r in reqs)
        probe = eng.routed_probe()
        assert len(probe["decode_rids"]) == BLOCK * len(
            probe["decode_blocks"])
        eng.shutdown()
    finally:
        mx.telemetry.disable()


# -- what the engine refuses ------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_k=2), "spec_k"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(adapters=2), "adapters"),
    (dict(host_kv_bytes=1 << 20), "host_kv_bytes"),
    (dict(temperature=0.7), "sampling"),
    (dict(prefill_chunk=6), "prefill_chunk"),
])
def test_refuses_what_a_diffusion_decoder_cannot_do(model, kw, what):
    with pytest.raises(ValueError, match=what):
        _engine(model, **kw)


def test_refuses_the_mask_id_and_short_prompts(model):
    eng = _engine(model)
    with pytest.raises(ValueError, match="mask id"):
        eng.submit(np.array([1, 2, MASK, 4, 5], np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="at least one block"):
        eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
    req = eng.submit(np.array([1, 2, 3, 4, 5], np.int32), max_new_tokens=6)
    assert req.target_len() == 12                  # whole blocks
    eng.shutdown()
