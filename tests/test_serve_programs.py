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
    spos = jnp.arange(S)[None, None, :]
    keep = spos <= pos[:, :, None]
    if window:
        keep = jnp.logical_and(keep, spos > pos[:, :, None] - window)
    if case == "dense_causal":
        # the prompt pass never reads the cache: keys are the R rows
        kb, vb, keep = kb[:, :R], vb[:, :R], keep[:, :, :R]
    operands = (q, kb, vb, keep)
    if not c["lead"]:
        operands = tuple(o[0] for o in operands)
    got = masked_attention(
        *operands, score_scale(DH) if scale is None else np.float32(scale))

    assert got.shape == operands[0].shape[:-2] + (HKV, HQ // HKV, DH)
    np.testing.assert_allclose(np.asarray(got).reshape(B * R, HQ, DH),
                               np.asarray(want), rtol=2e-5, atol=2e-6)


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
