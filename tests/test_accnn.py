"""accnn low-rank factorization tool (port of tools/accnn: acc_conv
vertical/horizontal SVD split, acc_fc two-FC split, DP rank selection)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import accnn  # noqa: E402

rng = np.random.RandomState(3)


def _forward(sym, params, data, label_shape=None):
    shapes = {"data": data.shape}
    exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    exe.arg_dict["data"][:] = data
    for k, v in params.items():
        if k in exe.arg_dict and k != "data":
            exe.arg_dict[k][:] = v.asnumpy() if hasattr(v, "asnumpy") else v
    return exe.forward(is_train=False)[0].asnumpy()


def _small_model(data_shape=(1, 3, 8, 8)):
    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             num_filter=4, pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=6, pad=(1, 1),
                             name="conv2")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc1")
    arg_shapes, _, _ = net.infer_shape(data=data_shape)
    arg_params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name != "data":
            arg_params[name] = mx.nd.array(
                rng.randn(*shape).astype(np.float32) * 0.3)
    return mx.model.FeedForward(symbol=net, arg_params=arg_params,
                                aux_params={}), arg_params


def test_conv_vh_full_rank_is_exact():
    model, params = _small_model()
    x = rng.randn(1, 3, 8, 8).astype(np.float32)
    ref = _forward(model.symbol, params, x)
    # conv1 weight viewed as (C*y, N*x) = (9, 12): full rank 9 -> exact
    new = accnn.conv_vh_decomposition(model, "conv1", K=9,
                                      data_shape=(1, 3, 8, 8))
    out = _forward(new.symbol, new.arg_params, x)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)
    # replaced layer's weights are gone, factor weights present
    assert "conv1_weight" not in new.arg_params
    assert "conv1_v_weight" in new.arg_params
    assert "conv1_h_weight" in new.arg_params


def test_conv_vh_low_rank_approximates():
    model, params = _small_model()
    x = rng.randn(1, 3, 8, 8).astype(np.float32)
    ref = _forward(model.symbol, params, x)
    errs = []
    for K in (2, 6, 9):
        new = accnn.conv_vh_decomposition(model, "conv1", K=K,
                                          data_shape=(1, 3, 8, 8))
        out = _forward(new.symbol, new.arg_params, x)
        errs.append(np.abs(out - ref).max())
    assert errs[2] < 1e-3
    assert errs[0] >= errs[1] >= errs[2]  # error shrinks with rank


def test_fc_decomposition_full_rank_exact():
    model, params = _small_model()
    x = rng.randn(1, 3, 8, 8).astype(np.float32)
    ref = _forward(model.symbol, params, x)
    new = accnn.fc_decomposition(model, "fc1", K=10,
                                 data_shape=(1, 3, 8, 8))
    out = _forward(new.symbol, new.arg_params, x)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)
    assert "fc1_red_weight" in new.arg_params
    assert "fc1_rec_bias" in new.arg_params


def test_rank_selection_respects_budget():
    model, _ = _small_model()
    sel = accnn.get_ranksel(model, ratio=2.0, data_shape=(1, 3, 8, 8))
    assert set(sel) == {"conv1", "conv2"}
    assert all(1 <= k for k in sel.values())
    # total factorized cost under original/ratio
    conf = json.loads(model.symbol.tojson())
    nodes = accnn.topsort(conf["nodes"])
    internals = model.symbol.get_internals()
    _, oshapes, _ = internals.infer_shape(data=(1, 3, 8, 8))
    out_shape = dict(zip(internals.list_outputs(), oshapes))
    total = used = 0
    for node in nodes:
        if node["op"] != "Convolution":
            continue
        data = [nodes[j[0]] for j in node["inputs"]
                if not nodes[j[0]]["name"].startswith(node["name"] + "_")][0]
        ishape = ((3, 8, 8) if accnn.is_input(data)
                  else tuple(out_shape[data["name"] + "_output"][1:]))
        per_rank, orig = accnn._conv_complexity(ishape, node)
        total += orig
        used += sel[node["name"]] * per_rank
    assert used <= total / 2.0


@pytest.mark.slow
def test_compress_end_to_end_and_cli(tmp_path):
    model, params = _small_model()
    x = rng.randn(2, 3, 8, 8).astype(np.float32)
    ref = _forward(model.symbol, params, x)
    new = accnn.compress(model, ratio=1.5, data_shape=(1, 3, 8, 8))
    out = _forward(new.symbol, new.arg_params, x)
    assert out.shape == ref.shape
    assert np.isfinite(out).all()

    # CLI round-trip through checkpoints
    prefix = str(tmp_path / "m")
    model.save(prefix, 1)
    out_prefix = str(tmp_path / "m-acc")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(accnn.__file__),
                                      "accnn.py"),
         "-m", prefix, "--load-epoch", "1", "--save-model", out_prefix,
         "--ratio", "1.5", "--data-shape", "1,3,8,8"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    loaded = mx.model.FeedForward.load(out_prefix, 1)
    out2 = _forward(loaded.symbol, loaded.arg_params, x)
    np.testing.assert_allclose(out2, out, rtol=1e-4, atol=1e-5)
