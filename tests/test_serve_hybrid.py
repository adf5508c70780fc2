"""Hybrid decoders (state-space + attention layers) through serve.Engine,
at a small size on the CPU: two periods of [m, m, a, m], widths of tens,
vocabulary under 100.  ``models/hybrid.py::reference_logits`` (float32,
token-by-token recurrence, no cache) is the yardstick throughout.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import hybrid as H
from mxnet_tpu.ops import ssm
from mxnet_tpu.serve import engine as engine_mod
from mxnet_tpu.serve.scheduler import FINISHED

VOCAB = 61
CHUNK = 8                          # the scan's chunk at this size


def _dec(vocab=VOCAB):
    return H.hybrid_decoder(
        vocab, 32, ["mamba", "mamba", "attention", "mamba"] * 2,
        num_heads=4, kv_heads=2, d_ff=48, mamba_heads=4, mamba_head_dim=16,
        mamba_state=16, mamba_chunk=CHUNK, embedding_multiplier=3.0,
        residual_multiplier=0.5, attention_multiplier=0.2,
        logits_scaling=2.0)


@pytest.fixture(scope="module")
def model():
    dec = _dec()
    return dec, dec.init_params(3, "float32")


def _engine(model, **kw):
    dec, params = model
    geo = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64,
               prefill_chunk=16)
    geo.update(kw)
    return mx.serve.Engine(params, symbol=dec, **geo)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


def _serve(eng, prompt, new):
    req = eng.submit(prompt, max_new_tokens=new)
    eng.run()
    assert req.status == FINISHED and len(req.tokens) == new
    return req


def _state(eng, slot):
    return (np.asarray(eng._state_ssm[:, slot]),
            np.asarray(eng._state_conv[:, slot]))


# -- the two formulations of the recurrence -----------------------------------

def _recurrence(x, dt, dA, Bm, Cm, D, S):
    ys = []
    for t in range(x.shape[0]):
        S = (np.exp(dA[t])[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * Bm[t][None, None, :])
        ys.append((S * Cm[t][None, None, :]).sum(-1) + D[:, None] * x[t])
    return np.stack(ys), S


@pytest.mark.parametrize("T", [CHUNK, CHUNK - 1, CHUNK + 1, 3 * CHUNK, 1])
def test_chunked_scan_equals_the_recurrence(T):
    """Lengths on, under and over a chunk edge, from a non-zero state:
    float32 both ways, so they agree to rounding (1e-5 of values of
    order 1)."""
    rng = np.random.default_rng(T)
    Hh, P, N = 4, 8, 16
    x = rng.normal(size=(T, Hh, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, size=(T, Hh)).astype(np.float32)
    dA = -dt * rng.uniform(1, 16, size=(Hh,)).astype(np.float32)
    Bm = rng.normal(size=(T, N)).astype(np.float32)
    Cm = rng.normal(size=(T, N)).astype(np.float32)
    D = rng.normal(size=(Hh,)).astype(np.float32)
    S0 = rng.normal(size=(Hh, P, N)).astype(np.float32)
    y_ref, S_ref = _recurrence(x, dt, dA, Bm, Cm, D, S0)
    y, S = ssm.ssd_chunked_scan(*map(jnp.asarray, (x, dt, dA, Bm, Cm, D, S0)),
                                CHUNK)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S), S_ref, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("slots", [
    [2, 4, 1],                      # every row live
    [2, 4, 1, 0, 0],                # dead rows behind the live ones
    [0, 2, 0, 4, 0, 1],             # dead rows in front of and between them
    [0, 0, 3, 0],                   # a single live row
], ids=["all-live", "trailing-dead", "dead-between", "one-live"])
def test_state_update_kernel_equals_the_jnp_update(slots):
    """The Mosaic kernel against the gather/scatter form: live slots
    agree to rounding, a dead row's (null slot 0's) ``y`` is exactly 0,
    and every slot no live row names is untouched, slot 0 included.
    With dead rows the kernel runs under TPU interpret mode, which copies
    a block in or out only when its index changes, as the chip does, and
    fills never-written buffers with NaN."""
    from jax.experimental.pallas import tpu as pltpu
    from mxnet_tpu.ops.pallas_ssm_update import ssm_update_kernel

    rng = np.random.default_rng(0)
    L, S, Hh, P, N, B = 3, 5, 8, 16, 128, len(slots)
    pool = jnp.asarray(rng.normal(size=(L, S, Hh, P, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, Hh, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(B, Hh)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    D = jnp.ones((Hh,), jnp.float32)
    sl = jnp.asarray(slots, jnp.int32)
    y0, p0 = ssm.ssm_state_update(pool, 1, sl, x, dt, -2 * dt, Bm, Cm, D,
                                  impl="jnp")
    mode = (pltpu.InterpretParams(uninitialized_memory="nan") if 0 in slots
            else None)
    with pltpu.force_tpu_interpret_mode(mode):
        y1, p1 = ssm_update_kernel(pool, 1, sl, x, dt, -2 * dt, Bm, Cm, D,
                                   heads_per_step=4, interpret=True)
    live = np.asarray(slots) != 0
    named = np.asarray(slots)[live]
    y0, p0, y1, p1 = map(np.asarray, (y0, p0, y1, p1))
    np.testing.assert_allclose(y1[live], y0[live], atol=1e-5)
    np.testing.assert_allclose(p1[1, named], p0[1, named], atol=1e-6)
    assert (y1[~live] == 0).all()
    others = [s for s in range(S) if s not in named]
    assert (p1[1, others] == np.asarray(pool)[1, others]).all()
    assert (p1[[0, 2]] == np.asarray(pool)[[0, 2]]).all()


# -- the engine against the reference ------------------------------------------

@pytest.mark.parametrize("plen,new", [(5, 6), (16, 4), (37, 5)])
def test_prefill_and_decode_through_the_caches_equal_the_reference(
        model, plen, new):
    """Logits, not tokens: the engine's greedy token at every generated
    position must be the reference's argmax up to rounding.  Everything
    is float32 on the CPU, so the reference logit of the engine's token
    is within 1e-4 of the reference's best (logits of order 1; the
    chunked scan and the cache reorder float32 sums, nothing more).
    37 tokens take three chunk passes (state carried between programs)."""
    dec, params = model
    eng = _engine(model)
    prompt = _prompt(plen, plen)
    req = _serve(eng, prompt, new)
    toks = np.concatenate([prompt, req.tokens])
    ref = np.asarray(dec.reference_logits(params, toks[:-1]))[plen - 1:]
    regret = ref.max(-1) - ref[np.arange(new), req.tokens]
    assert regret.max() <= 1e-4, regret
    assert req.prefill_passes == (1 if plen <= 16 else 3)
    eng.shutdown()


def test_a_prompt_split_over_chunk_passes_equals_one_pass(model):
    """The same prompt chunked (prefill_chunk 16: three passes) and whole
    (prefill_chunk 0): same tokens, and the slot holds the same state
    (float32 sums in another order: 1e-5)."""
    prompt = _prompt(11, 37)
    out = []
    for chunk in (16, 0):
        eng = _engine(model, prefill_chunk=chunk)
        req = eng.submit(prompt, max_new_tokens=1)
        eng.step()
        while not req.tokens:
            eng.step()
        slot = eng.blocks.state_slot(req.rid) if not req.done else None
        out.append((req.tokens[0], req.prefill_passes,
                    _state(eng, 1)))           # the first slot handed out
        assert slot in (None, 1)
        eng.shutdown()
    (t_c, passes_c, (s_c, c_c)), (t_w, passes_w, (s_w, c_w)) = out
    assert (passes_c, passes_w) == (3, 1) and t_c == t_w
    np.testing.assert_allclose(s_c, s_w, atol=1e-5)
    np.testing.assert_allclose(c_c, c_w, atol=1e-6)


def test_padding_moves_nothing(model):
    """The same 9-token prompt in the 16-bucket and, through a larger
    ``max_model_len`` floor, in a 64-bucket program leaves the same state
    and the same first token: padded positions have dt 0 and the
    convolution's rows are taken at the last real position."""
    from mxnet_tpu.serve import hybrid as SH

    dec, params = model
    eng = _engine(model)
    prompt = _prompt(5, 9)
    outs = []
    for bucket in (16, 64):
        fn = SH.build_prefill(eng._cfg, bucket, False)
        toks = np.zeros(bucket, np.int32)
        toks[:9] = prompt
        blk = np.zeros(bucket, np.int32)
        blk[:9] = 1 + np.arange(9) // 4
        off = (np.arange(bucket) % 4).astype(np.int32)
        res = fn(eng.params, *eng._cache_args(), jnp.asarray(toks),
                 jnp.asarray(9, jnp.int32), jnp.asarray(blk),
                 jnp.asarray(off), jnp.asarray(2, jnp.int32), eng._key)
        outs.append((int(res[0]), np.asarray(res[3][:, 2]),
                     np.asarray(res[4][:, 2])))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=1e-5)
    np.testing.assert_allclose(outs[0][2], outs[1][2], atol=1e-5)
    eng.shutdown()


def test_a_reused_slot_starts_from_zero(model):
    """max_batch 1: the second request takes the slot the first gave
    back, full of the first one's state, and must answer as if alone."""
    eng = _engine(model, max_batch=1)
    a = _serve(eng, _prompt(1, 20), 6)
    assert np.abs(_state(eng, 1)[0]).max() > 0          # left behind
    b = _serve(eng, _prompt(2, 7), 6)
    fresh = _engine(model, max_batch=1)
    alone = _serve(fresh, _prompt(2, 7), 6)
    assert b.tokens == alone.tokens and a.tokens != b.tokens
    assert eng.blocks.state_slots_in_use == 0
    eng.shutdown()
    fresh.shutdown()


def test_a_preempted_request_equals_the_unpreempted_run(model):
    """A cache too small for both: the later arrival is preempted, gives
    blocks and slot back, and its resume prefills prompt plus generated
    tokens from a zeroed slot: same tokens as when served alone."""
    prompts = [_prompt(21, 14), _prompt(22, 13)]
    alone = []
    for p in prompts:
        eng = _engine(model)
        alone.append(_serve(eng, p, 12).tokens)
        eng.shutdown()
    eng = _engine(model, num_blocks=11)         # 40 tokens of cache
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run()
    assert eng.scheduler.preemptions >= 1
    assert [r.tokens for r in reqs] == alone
    assert eng.blocks.state_slots_in_use == 0
    eng.shutdown()


def test_requests_arriving_while_others_decode_equal_serving_alone(model):
    """Eight requests over four slots, submitted two per step while the
    earlier ones decode (continuous batching of scan layers, slot
    turnover, one chunked prompt): each equals the same request alone."""
    shapes = [(5, 6), (9, 3), (23, 5), (4, 8), (12, 4), (7, 7), (16, 2),
              (3, 9)]
    prompts = [_prompt(100 + i, n) for i, (n, _) in enumerate(shapes)]
    alone = []
    for p, (_, g) in zip(prompts, shapes):
        eng = _engine(model)
        alone.append(_serve(eng, p, g).tokens)
        eng.shutdown()
    eng = _engine(model)
    reqs = []
    for i in range(0, 8, 2):
        reqs += [eng.submit(prompts[j], max_new_tokens=shapes[j][1])
                 for j in (i, i + 1)]
        eng.step()
        eng.step()
    eng.run()
    assert [r.tokens for r in reqs] == alone
    eng.shutdown()


@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_k=2, draft_params={}), "spec_k"),
    (dict(adapters=2), "adapters"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(quantize="int8"), "quantize"),
    (dict(tp=2), "tp"),
    (dict(host_kv_bytes=1 << 20), "host_kv_bytes"),
])
def test_what_a_hybrid_engine_refuses(model, kw, word):
    with pytest.raises(ValueError, match=word):
        _engine(model, **kw)


def test_block_export_and_import_are_refused(model):
    eng = _engine(model)
    assert eng.blocks.prefix_cache is False
    assert "state" in eng.statusz()["prefix_cache"]["disabled_reason"]
    with pytest.raises(ValueError, match="export"):
        eng.blocks.export_blocks(0, [1, 2, 3])
    with pytest.raises(ValueError, match="import"):
        eng.ingest_pulled_blocks([])
    eng.shutdown()


def test_state_spans_counters_and_statusz(model):
    mx.telemetry.enable()
    try:
        mx.telemetry.tracer().clear()
        eng = _engine(model, num_blocks=11)
        reqs = [eng.submit(_prompt(21 + i, 14 - i), max_new_tokens=12)
                for i in range(2)]
        eng.step()
        sc = eng.statusz()["state_cache"]
        # the step loop runs one pass ahead: the second request's
        # admission (the next pass's) is made before the first returns
        assert sc["slots"] == 4 and sc["in_use"] == 2
        assert sc["ssm_dtype"] == "float32"
        assert sc["bytes_total"] == (eng._state_ssm.nbytes
                                     + eng._state_conv.nbytes)
        eng.run()
        spans = mx.telemetry.tracer().spans(prefix="serve.")
        steps = [s for s in spans if s[0] == "serve.step"]
        assert all("state_slots" in s[5] for s in steps)
        assert max(s[5]["state_slots"] for s in steps) == 2
        states = [s[5]["state"] for s in spans if s[0] == "serve.prefill"]
        assert states.count("fresh") == 2 and "reset" in states
        text = mx.telemetry.to_prometheus_text(mx.telemetry.registry())
        assert 'mxtpu_serve_state_resets_total{reason="admit"} 2' in text
        assert 'mxtpu_serve_state_resets_total{reason="preempt"}' in text
        assert "mxtpu_serve_state_slots_in_use 0" in text
        assert all(r.status == FINISHED for r in reqs)
        eng.shutdown()
    finally:
        mx.telemetry.disable()


def _skipped_total():
    text = mx.telemetry.to_prometheus_text(mx.telemetry.registry())
    m = re.search(r"^mxtpu_serve_state_updates_skipped_total (\S+)$", text,
                  re.M)
    return float(m.group(1)) if m else 0.0


def test_a_padded_decode_row_is_skipped_and_counted(model, monkeypatch):
    """Three requests at once in the 4-row bucket, so a decode pass of
    all three has one dead row: the state kernel (interpreted, a group
    over two head steps) runs inside the engine's programs, every
    generated position equals the reference, ``serve.decode`` says the
    pass skipped one row, and the counter rises by the state layers for
    each such pass."""
    from mxnet_tpu.ops.pallas_ssm_update import ssm_update_kernel

    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    monkeypatch.setattr(ssm, "ssm_state_update",
                        lambda pool, layer, slots, *a: ssm_update_kernel(
                            pool, layer, slots, *a, heads_per_step=2,
                            interpret=True))
    dec, params = model
    mx.telemetry.enable()
    try:
        mx.telemetry.tracer().clear()
        before = _skipped_total()
        eng = _engine(model)
        prompts = [_prompt(60 + i, n) for i, n in enumerate((6, 9, 4))]
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        for prompt, req in zip(prompts, reqs):
            toks = np.concatenate([prompt, req.tokens])
            ref = np.asarray(dec.reference_logits(params, toks[:-1]))
            ref = ref[len(prompt) - 1:]
            regret = ref.max(-1) - ref[np.arange(6), req.tokens]
            assert regret.max() <= 1e-4, regret
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


# -- the gpt engines keep their programs ---------------------------------------

def _gpt_engine(vocab):
    net = mx.models.gpt(vocab, 32, num_layers=2, d_model=32, num_heads=4,
                        d_ff=64, norm="rmsnorm", mlp="swiglu",
                        pos_embed="rope", kv_heads=2)
    shapes, _, _ = net.infer_shape(data=(1, 32), softmax_label=(1, 32))
    rng = np.random.default_rng(0)
    params = {n: (rng.normal(size=s) * 0.1).astype(np.float32)
              for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    return mx.serve.Engine(params, symbol=net, block_size=4, num_blocks=32,
                           max_batch=2, max_model_len=32)


def test_gpt_fingerprint_and_cfg_are_the_parents():
    """No hybrid field reaches a gpt() engine's AOT fingerprint (its
    digests stay where they were), and its _ModelCfg says hybrid=None.
    The fingerprint's keys and cfg fields are the parent commit's,
    literally."""
    eng = _gpt_engine(53)
    fp = eng._aot_base_fp()
    assert eng._cfg.hybrid is None
    assert sorted(fp["cfg"]) == sorted([
        "name", "n_layers", "num_heads", "head_dim", "kv_heads",
        "pos_table", "swiglu", "tied", "rmsnorm", "window", "block_size",
        "numeric_watch", "temperature", "top_k"])
    assert sorted(fp) == ["backend", "cache_dtype", "cfg", "donate", "format",
                          "jax_version", "num_blocks", "subsystem",
                          "table_width"]
    assert "state_slots" not in fp and "hybrid" not in str(fp)
    # _spec_key: the parent's tuple, then the parameters' signature and
    # the (absent) state pool
    key = eng._spec_key()
    assert key[0] is eng._cfg and key[-1] is None
    eng.shutdown()


def test_two_vocabularies_in_one_process(monkeypatch):
    """_spec_key() left the vocabulary out: the second engine took the
    first one's compiled programs and failed at its first dispatch."""
    monkeypatch.setattr(engine_mod, "_STEP_CACHE", {})
    a, b = _gpt_engine(53), _gpt_engine(71)
    assert a._spec_key() != b._spec_key()
    assert a._aot_base_fp() == b._aot_base_fp()      # digests did not move
    for eng, vocab in ((a, 53), (b, 71)):
        req = eng.submit(np.arange(5) % vocab, max_new_tokens=3)
        eng.run()
        assert len(req.tokens) == 3 and max(req.tokens) < vocab
    a.shutdown()
    b.shutdown()


def test_hybrid_engines_key_on_their_description(model):
    dec, params = model
    a = _engine(model)
    other = _dec(vocab=67)
    b = mx.serve.Engine(other.init_params(3), symbol=other, block_size=4,
                        num_blocks=64, max_batch=4, max_model_len=64,
                        prefill_chunk=16)
    assert a._spec_key() != b._spec_key()
    assert a._aot_base_fp() != b._aot_base_fp()
    assert a._aot_base_fp()["cfg"]["hybrid"]["vocab_size"] == VOCAB
    assert a._aot_base_fp()["state_slots"] == 5
    a.shutdown()
    b.shutdown()


def test_served_behind_the_replica_server(model):
    """POST /generate of fleet.ReplicaServer over a hybrid engine: the
    normal path, no side script."""
    import json
    import urllib.request

    from mxnet_tpu import fleet

    eng = _engine(model)
    alone = _serve(eng, _prompt(7, 6), 4).tokens
    srv = fleet.ReplicaServer(eng, port=0).start()
    try:
        body = json.dumps({"prompt": [int(t) for t in _prompt(7, 6)],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = json.loads(resp.read())
        assert out["tokens"] == alone
    finally:
        srv.stop()
