"""The layer below the serving engine (mxnet_tpu/serve/programs.py):

* ``ops.attention.masked_attention``, the ONE span attention under the
  prefill, chunk and verify programs of both decoders, against
  ``paged_attention(impl="jnp")`` as the oracle: a span row at cache
  position ``p`` is the decode row whose context is ``p + 1``;
* the arrows point one way: ``engine -> {spec, hybrid} -> programs ->
  ops``, so nothing below the engine imports it, at any depth.

That the programs built from these pieces serve the right tokens is the
serve suites' business (test_serve.py, test_prefix_cache.py,
test_spec_decode.py, test_quant_serve.py, test_adapters.py,
test_serve_hybrid.py: each compares against models/generate.py).
"""

import ast
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.attention import (masked_attention, paged_attention,
                                     score_scale)

HQ, HKV, DH, BS = 4, 2, 8, 4

# name -> (requests B, table slots W, first position of each request's
# rows, rows a request, window, scale, keep the leading axis)
SPANS = {
    # a whole prompt from position 0: the keys are the span's own rows
    "dense_causal": dict(B=1, W=3, start=[0], rows=12, lead=False),
    # a chunk whose first row sits mid-block, over a longer table
    "table_span_inside_a_block": dict(B=1, W=6, start=[5], rows=8,
                                      lead=False),
    "sliding_window": dict(B=1, W=6, start=[7], rows=8, window=5,
                           lead=False),
    # verify: k+1 rows of each of B requests, every request its table
    "leading_axis": dict(B=3, W=5, start=[2, 9, 14], rows=4, lead=True),
    "explicit_scale": dict(B=1, W=4, start=[3], rows=6, scale=0.37,
                           lead=False),
}


@pytest.mark.parametrize("case", sorted(SPANS))
def test_masked_attention_matches_the_paged_oracle(case):
    c = SPANS[case]
    B, W, R = c["B"], c["W"], c["rows"]
    window, scale = c.get("window", 0), c.get("scale")
    S = W * BS
    rng = np.random.RandomState(sorted(SPANS).index(case))
    nb = 1 + B * W
    ck = jnp.asarray(rng.randn(nb, BS, HKV, DH), jnp.float32)
    cv = jnp.asarray(rng.randn(nb, BS, HKV, DH), jnp.float32)
    # every request its own blocks, out of order; block 0 is the null one
    tables = jnp.asarray(1 + rng.permutation(B * W).reshape(B, W), jnp.int32)
    pos = jnp.asarray(c["start"], jnp.int32)[:, None] + jnp.arange(R)
    assert int(pos.max()) < S
    q = jnp.asarray(rng.randn(B, R, HQ, DH), jnp.float32)

    want = paged_attention(
        q.reshape(B * R, HQ, DH), ck, cv, jnp.repeat(tables, R, axis=0),
        (pos + 1).reshape(-1), window=window, scale=scale, impl="jnp")

    kb = ck[tables].reshape(B, S, HKV, DH)
    vb = cv[tables].reshape(B, S, HKV, DH)
    start = jnp.asarray(c["start"], jnp.int32)
    if case == "dense_causal":
        # the prompt pass never reads the cache: keys are the R rows
        kb, vb = kb[:, :R], vb[:, :R]
    operands = (q, kb, vb, start)
    if not c["lead"]:
        operands = tuple(o[0] for o in operands)
    got = masked_attention(
        *operands, score_scale(DH) if scale is None else np.float32(scale),
        window=window)

    assert got.shape == operands[0].shape[:-2] + (HKV, HQ // HKV, DH)
    np.testing.assert_allclose(np.asarray(got).reshape(B * R, HQ, DH),
                               np.asarray(want), rtol=2e-5, atol=2e-6)


# -- the span kernel's walk against the dense branch ---------------------------
# rows T over keys S at heads (Hq, Hkv, Dh), tiles (block_q, block_k) of
# `resident` keys a grid step; the first `n_valid` rows real
WALKS = {
    "start_0": dict(T=64, S=256, start=0, n_valid=64),
    "start_tile_aligned": dict(T=64, S=512, start=128, n_valid=64),
    "start_not_aligned": dict(T=64, S=512, start=131, n_valid=64),
    "fewer_real_rows_than_the_bucket": dict(T=64, S=512, start=131,
                                            n_valid=10),
    "sliding_window": dict(T=64, S=512, start=300, n_valid=50, window=100),
    "window_wider_than_the_context": dict(T=64, S=256, start=40, n_valid=64,
                                          window=400),
    "view_longer_than_the_context": dict(T=32, S=1024, start=70, n_valid=20),
    "whole_prompt": dict(T=128, S=128, start=0, n_valid=77),
    "keys_in_several_resident_steps": dict(T=64, S=512, start=300,
                                           n_valid=64, resident=128),
    "several_steps_under_a_window": dict(T=64, S=512, start=300, n_valid=64,
                                         resident=128, window=150),
    "heads_32_8_128": dict(T=32, S=256, start=37, n_valid=30, heads=(32, 8)),
    "bfloat16": dict(T=64, S=256, start=100, n_valid=50, dtype="bfloat16"),
}


def _walk_operands(c, Dh=128):
    Hq, Hkv = c.get("heads", (4, 2))
    dtype = c.get("dtype", "float32")
    rng = np.random.RandomState(sorted(WALKS).index(c["name"]))
    q = jnp.asarray(rng.randn(c["T"], Hq, Dh), dtype)
    k = jnp.asarray(rng.randn(c["S"], Hkv, Dh), dtype)
    v = jnp.asarray(rng.randn(c["S"], Hkv, Dh), dtype)
    return q, k, v


@pytest.mark.parametrize("case", sorted(WALKS))
def test_span_kernel_walk_matches_the_dense_branch(case):
    from mxnet_tpu.ops.pallas_span_attention import span_attention_kernel

    c = dict(WALKS[case], name=case)
    q, k, v = _walk_operands(c)
    scale, window, n = score_scale(128), c.get("window", 0), c["n_valid"]
    # off the chip masked_attention IS the dense branch
    want = masked_attention(q, k, v, c["start"], scale, window=window)
    got = span_attention_kernel(
        q, k, v, jnp.int32(c["start"]), jnp.int32(n), scale, window=window,
        block_q=32, block_k=128, resident=c.get("resident", c["S"]),
        interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 2e-2 if c.get("dtype") == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=tol, atol=tol)
    # padding rows: nobody reads them, but a NaN there would poison the
    # next layer's K/V rows and the watchdog's flag
    assert np.isfinite(np.asarray(got, np.float32)).all()


def test_span_kernel_serves_heads_of_64_padded_to_the_lanes():
    """32 / 8 heads x 64 (the hybrid's attention layers): a head is half a
    lane tile; zero-padded to 128 in front of the call the same kernel
    is exact.  Heads that are neither are refused."""
    from mxnet_tpu.ops.pallas_span_attention import span_attention_kernel

    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(32, 32, 64), jnp.float32)
    k = jnp.asarray(rng.randn(256, 8, 64), jnp.float32)
    v = jnp.asarray(rng.randn(256, 8, 64), jnp.float32)
    want = masked_attention(q, k, v, 100, np.float32(1 / 64.0))
    got = span_attention_kernel(q, k, v, 100, 32, np.float32(1 / 64.0),
                                block_q=32, block_k=128, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="heads of 32"):
        span_attention_kernel(q[..., :32], k[..., :32], v[..., :32], 100,
                              32, np.float32(1 / 64.0), block_q=32,
                              block_k=128, interpret=True)


@pytest.mark.parametrize("case", sorted(
    k for k in WALKS if "resident" not in WALKS[k]))
def test_span_kv_tiles_counts_the_tiles_the_mask_keeps(case, monkeypatch):
    """``serve.prefill``'s ``kv_tiles`` is host arithmetic: held here to
    the mask itself, a tile counting when a real row keeps a key of it."""
    from mxnet_tpu.ops import attention as att

    c = WALKS[case]
    T, S, start, n = c["T"], c["S"], c["start"], c["n_valid"]
    window = c.get("window", 0)
    monkeypatch.setattr(att, "SPAN_BLOCK_Q", 32)
    monkeypatch.setattr(att, "SPAN_BLOCK_K", 128)
    keep = np.asarray(att._span_keep(start, T, S, window))[:n]
    live = 0
    for r0 in range(0, n, 32):
        # from the first row's first key to the last real row's last
        first = np.flatnonzero(keep[r0])[0]
        last = np.flatnonzero(keep[min(r0 + 32, n) - 1])[-1]
        live += last // 128 - first // 128 + 1
    assert att.span_kv_tiles(T, S, start, n, window) == (
        live, (T // 32) * (S // 128))
    assert att.span_kv_tiles(T, S, start, n, window, "dense") == (
        (T // 32) * (S // 128),) * 2


def test_the_span_branch_follows_backend_and_shapes_only():
    from mxnet_tpu.ops.attention import (SPAN_KERNEL_MIN_SCORES,
                                         resolve_span_impl)
    from tools.hlo_audit import assume_tpu

    assert SPAN_KERNEL_MIN_SCORES == 128 * 4096
    assert resolve_span_impl(2048, 4096, 128) == "dense"    # off the chip
    with assume_tpu():
        assert resolve_span_impl(2048, 4096, 128) == "kernel"
        assert resolve_span_impl(128, 4096, 128) == "kernel"
        assert resolve_span_impl(64, 4096, 128) == "dense"
        assert resolve_span_impl(1024, 1024, 128) == "kernel"
        assert resolve_span_impl(512, 512, 128) == "dense"
        assert resolve_span_impl(3, 4096, 128) == "dense"   # verify's rows
        assert resolve_span_impl(512, 4096, 64) == "kernel"  # padded heads
        assert resolve_span_impl(512, 4096, 32) == "dense"
        assert resolve_span_impl(2048, 3000, 128) == "dense"


# -- an engine through the kernel branch ---------------------------------------

def _kernel_branch(patch):
    """Every span the kernel's blocks tile goes through the kernel,
    whatever its size, in the interpreter (``on_tpu()`` stays False: no chip
    here).  The one name ``masked_attention`` and the engine both ask."""
    from mxnet_tpu.ops import attention as att

    patch.setattr(
        att, "resolve_span_impl",
        lambda T, S, head_dim: ("kernel" if att.span_kernel_eligible(
            T, S, head_dim, min_scores=0) else "dense"))


def _span_engine(heads=(2, 1), **kw):
    """Heads of 128 (the kernel's lanes) at a toy width: 2 query heads
    on 1 kv head, two layers, a 128-position table of 16-token blocks."""
    net = mx.models.gpt(61, 128, num_layers=2, d_model=128 * heads[0],
                        num_heads=heads[0], kv_heads=heads[1],
                        pos_embed="rope", norm="rmsnorm")
    shapes, _, _ = net.infer_shape(data=(1, 128), softmax_label=(1, 128))
    rng = np.random.RandomState(0)
    params = {n: (rng.randn(*s) * 0.1 + n.endswith("gamma")).astype(
        np.float32) for n, s in zip(net.list_arguments(), shapes)
        if n not in ("data", "softmax_label")}
    return mx.serve.Engine(params, symbol=net, block_size=16, num_blocks=32,
                           max_batch=2, max_model_len=128, **kw)


def _serve(eng, prompt):
    try:
        req = eng.submit(prompt, max_new_tokens=4)
        eng.run()
        return list(req.tokens), eng.statusz(), eng._aot_base_fp()
    finally:
        eng.shutdown()


def test_chunked_prompt_through_the_kernel_emits_the_whole_prompt_tokens(
        monkeypatch):
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.attention import (SPAN_BLOCK_K, SPAN_BLOCK_Q,
                                         SPAN_KERNEL_MIN_SCORES)

    prompt = np.random.RandomState(1).randint(0, 61, (80,)).astype(np.int32)
    # the whole prompt in one dense pass: what every branch must emit
    whole, _, _ = _serve(_span_engine(prefill_chunk=0), prompt)
    _kernel_branch(monkeypatch)
    telemetry.reset()
    telemetry.enable()
    try:
        got, status, fp = _serve(_span_engine(prefill_chunk=32), prompt)
        spans = telemetry.tracer().spans(prefix="serve.prefill")
    finally:
        telemetry.disable()
        telemetry.reset()
    # 80 tokens in chunks of 32, 32 and 16 rows over the 128-key view;
    # a pass is one (rows, 128) tile a head, and all of them are live
    passes = [s[5] for s in spans if s[0] == "serve.prefill"]
    assert [(a["kind"], a["bucket"], a["attn"], a["kv_tiles"],
             a["kv_tiles_table"]) for a in passes] == [
        ("chunk", 32, "kernel", 1, 1), ("chunk", 32, "kernel", 1, 1),
        ("chunk", 16, "kernel", 1, 1)]
    assert status["span_attention"] == "kernel"
    assert status["span_kernel_min_scores"] == SPAN_KERNEL_MIN_SCORES
    assert fp["span_attention"] == (f"pallas-q{SPAN_BLOCK_Q}k{SPAN_BLOCK_K}"
                                    f"-from{SPAN_KERNEL_MIN_SCORES}")
    assert got == whole
    # the whole prompt in one pass is a (128, 128) span: the kernel too
    assert _serve(_span_engine(prefill_chunk=0), prompt)[0] == whole


def test_kernel_branch_runs_per_head_shard_under_tp(monkeypatch):
    """GSPMD cannot partition a Mosaic call: under ``tp`` the chunk and
    prefill programs run the kernel inside a ``shard_map`` over the head
    axis (2 of 4 query heads and 1 of 2 kv heads a shard), and emit what
    one device emits."""
    prompt = np.random.RandomState(2).randint(0, 61, (80,)).astype(np.int32)
    whole, _, _ = _serve(_span_engine((4, 2), prefill_chunk=0), prompt)
    _kernel_branch(monkeypatch)
    eng = _span_engine((4, 2), prefill_chunk=32, tp=2)
    text = eng._program_builder("chunk", 32).trace(
        *eng._program_specs("chunk", 32)).jaxpr.pretty_print(
            use_color=False)
    assert "shard_map" in text and "span_attention" in text
    got, status, _ = _serve(eng, prompt)
    assert status["span_attention"] == "kernel"
    assert got == whole


def test_dense_engine_says_so_and_keeps_its_fingerprint():
    prompt = np.random.RandomState(1).randint(0, 61, (80,)).astype(np.int32)
    from mxnet_tpu import telemetry

    telemetry.reset()
    telemetry.enable()
    try:
        got, status, fp = _serve(_span_engine(prefill_chunk=32), prompt)
        spans = telemetry.tracer().spans(prefix="serve.prefill")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert status["span_attention"] == "dense"
    assert status["span_kernel_min_scores"] is None
    assert "span_attention" not in fp
    passes = [s[5] for s in spans if s[0] == "serve.prefill"]
    assert [(a["attn"], a["kv_tiles"], a["kv_tiles_table"])
            for a in passes] == [("dense", 1, 1)] * 3
    # off the chip this IS the tokens the kernel branch must emit
    whole, _, _ = _serve(_span_engine(prefill_chunk=0), prompt)
    assert got == whole


SERVE = os.path.join(os.path.dirname(os.path.abspath(mx.__file__)), "serve")
# module -> the serve modules above it, which it must not import
ABOVE = {"programs": {"engine", "spec", "hybrid"},
         "spec": {"engine"},
         "hybrid": {"engine"}}


def _serve_imports(tree):
    """Names of the ``mxnet_tpu.serve`` modules a module's source imports,
    wherever the statement stands (top level or a function's body)."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 1:            # from .x import y / from . import x
                names = ([f"mxnet_tpu.serve.{mod}"] if mod else
                         [f"mxnet_tpu.serve.{a.name}" for a in node.names])
            elif node.level == 2:          # from ..serve.x import y
                names = [f"mxnet_tpu.{mod}"] + [
                    f"mxnet_tpu.{mod}.{a.name}" for a in node.names]
            else:
                names = [mod] + [f"{mod}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            parts = name.split(".")
            if parts[:2] == ["mxnet_tpu", "serve"] and len(parts) > 2:
                found.add(parts[2])
    return found


@pytest.mark.parametrize("module", sorted(ABOVE))
def test_nothing_below_the_engine_imports_it(module):
    with open(os.path.join(SERVE, f"{module}.py")) as f:
        tree = ast.parse(f.read())
    upward = _serve_imports(tree) & ABOVE[module]
    assert not upward, (f"serve/{module}.py imports {sorted(upward)}: the "
                        "arrows are engine -> {spec, hybrid} -> programs")


def test_the_layering_check_sees_every_form_of_import():
    src = ("import mxnet_tpu.serve.engine\n"
           "def f():\n"
           "    from .spec import x\n"
           "    from . import hybrid as H\n"
           "    from ..serve.adapters import y\n"
           "    from mxnet_tpu.serve import stats\n"
           "    from ..ops import attention\n")
    assert _serve_imports(ast.parse(src)) == {
        "engine", "spec", "hybrid", "adapters", "stats"}
