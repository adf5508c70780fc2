"""Post-training int8 quantization (contrib/quantization.py +
ops/quantized.py — beyond the 2016 reference; the contrib/quantize.py
capability of later MXNet, rebuilt TPU-native)."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.contrib.quantization import quantize_model, quantize_weight


def test_quantize_weight_roundtrip():
    rng = np.random.RandomState(0)
    w = rng.randn(8, 32).astype(np.float32)
    wq, scale = quantize_weight(w)
    assert wq.dtype == np.int8 and scale.shape == (8,)
    deq = wq.astype(np.float32) * scale[:, None]
    # per-channel symmetric int8: max error is half a quantization step
    step = scale[:, None]
    assert np.all(np.abs(deq - w) <= step * 0.5 + 1e-7)
    # zero rows quantize cleanly (scale falls back to 1)
    wq0, s0 = quantize_weight(np.zeros((2, 4), np.float32))
    assert np.all(wq0 == 0) and np.all(s0 == 1.0)


def _trained_mlp():
    rng = np.random.RandomState(1)
    X = rng.randn(256, 20).astype(np.float32)
    y = (X[:, :4].sum(1) > 0).astype(np.float32)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=32,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, 64), num_epoch=10, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier())
    args, aux = mod.get_params()
    probs = mod.predict(mx.io.NDArrayIter(X, None, 64)).asnumpy()
    return net, args, aux, X, y, probs


def _run_quantized(qsym, qargs, X):
    exe = qsym.simple_bind(mx.cpu(), grad_req="null", data=X.shape,
                           softmax_label=(X.shape[0],))
    for k, v in qargs.items():
        if k in exe.arg_dict:
            exe.arg_dict[k][:] = v
    exe.arg_dict["data"][:] = X
    return exe, exe.forward(is_train=False)[0].asnumpy()


def test_weight_only_fc_close_to_float():
    net, args, aux, X, y, probs_f = _trained_mlp()
    qsym, qargs, _ = quantize_model(net, args, aux)
    # weights really stored int8; scale vectors appear
    assert qargs["fc1_weight"].dtype == np.int8
    assert qargs["fc1_wscale"].shape == (32,)
    assert "wscale" in " ".join(qsym.list_arguments())
    exe, probs_q = _run_quantized(qsym, qargs, X)
    # int8 weight noise is tiny for a 2-layer MLP
    assert np.abs(probs_q - probs_f).max() < 0.05
    assert (probs_q.argmax(1) == probs_f.argmax(1)).mean() > 0.98


def test_calibrated_int8_fc():
    net, args, aux, X, y, probs_f = _trained_mlp()
    qsym, qargs, _ = quantize_model(net, args, aux,
                                    calib_data=[X[:64], X[64:128]])
    # act_scale baked into the graph
    import json

    conf = json.loads(qsym.tojson())
    scales = [float(n["param"]["act_scale"]) for n in conf["nodes"]
              if n["op"] == "QuantizedFullyConnected"]
    assert len(scales) == 2 and all(s > 0 for s in scales)
    exe, probs_q = _run_quantized(qsym, qargs, X)
    acc_f = (probs_f.argmax(1) == y).mean()
    acc_q = (probs_q.argmax(1) == y).mean()
    assert acc_q >= acc_f - 0.03, (acc_f, acc_q)


def test_quantized_conv_net():
    rng = np.random.RandomState(2)
    X = rng.randn(64, 1, 8, 8).astype(np.float32)
    y = rng.randint(0, 4, 64).astype(np.float32)
    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             num_filter=8, pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, 32), num_epoch=3,
            initializer=mx.initializer.Xavier())
    args, aux = mod.get_params()
    probs_f = mod.predict(mx.io.NDArrayIter(X, None, 32)).asnumpy()

    for calib in (None, [X[:32]]):
        qsym, qargs, _ = quantize_model(net, args, aux, calib_data=calib)
        assert qargs["conv1_weight"].dtype == np.int8
        exe, probs_q = _run_quantized(qsym, qargs, X)
        assert (probs_q.argmax(1) == probs_f.argmax(1)).mean() > 0.95, \
            ("calib" if calib else "weight-only")


def test_exclude_and_ineligible_pass_through():
    import json

    data = mx.sym.Variable("data")
    # grouped conv: structurally ineligible
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=4,
                             num_group=2, pad=(1, 1), name="gconv")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3,
                                name="fc_keep")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc_q")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = {n: s for n, s in zip(
        net.list_arguments(),
        net.infer_shape(data=(2, 4, 6, 6))[0])}
    rng = np.random.RandomState(3)
    args = {n: mx.nd.array(rng.randn(*shapes[n]).astype(np.float32))
            for n in shapes if n not in ("data", "softmax_label")}
    qsym, qargs, _ = quantize_model(net, args, exclude=("fc_keep",))
    ops = {n["name"]: n["op"] for n in json.loads(qsym.tojson())["nodes"]}
    assert ops["gconv"] == "Convolution"          # ineligible: grouped
    assert ops["fc_keep"] == "FullyConnected"     # excluded by name
    assert ops["fc_q"] == "QuantizedFullyConnected"
    assert qargs["fc_keep_weight"].dtype == np.float32
    assert qargs["fc_q_weight"].dtype == np.int8


def test_quantized_checkpoint_roundtrip(tmp_path):
    """int8 params survive the standard two-artifact checkpoint."""
    net, args, aux, X, y, _ = _trained_mlp()
    qsym, qargs, qaux = quantize_model(net, args, aux)
    prefix = str(tmp_path / "quant")
    qsym.save(prefix + "-symbol.json")
    mx.nd.save(prefix + "-0000.params",
               {"arg:" + k: v for k, v in qargs.items()})
    sym2 = mx.sym.load(prefix + "-symbol.json")
    loaded = mx.nd.load(prefix + "-0000.params")
    args2 = {k[4:]: v for k, v in loaded.items()}
    assert args2["fc1_weight"].dtype == np.int8
    _, p1 = _run_quantized(qsym, qargs, X)
    _, p2 = _run_quantized(sym2, args2, X)
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_quantized_conv_nhwc_and_ragged_calibration():
    """NHWC layout (weights stay OIHW like the float op) and a ragged
    final calibration batch both work."""
    rng = np.random.RandomState(4)
    X = rng.randn(48, 8, 8, 2).astype(np.float32)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3,), num_filter=4, pad=(1, 1),
                             layout="NHWC", name="cq")  # 1-tuple kernel
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fq")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(48, 8, 8, 2))[0]))
    args = {n: mx.nd.array(rng.randn(*shapes[n]).astype(np.float32) * 0.3)
            for n in shapes if n not in ("data", "softmax_label")}

    exe_f = net.simple_bind(mx.cpu(), grad_req="null", data=(48, 8, 8, 2),
                            softmax_label=(48,))
    for k, v in args.items():
        exe_f.arg_dict[k][:] = v
    exe_f.arg_dict["data"][:] = X
    probs_f = exe_f.forward(is_train=False)[0].asnumpy()

    for calib in (None, [X[:32], X[32:48]]):   # ragged second batch
        qsym, qargs, _ = quantize_model(net, args, calib_data=calib)
        assert qargs["cq_weight"].dtype == np.int8
        # quantization is shape-preserving: OIHW in both layouts
        assert tuple(qargs["cq_weight"].shape) == tuple(args["cq_weight"].shape)
        exe, probs_q = _run_quantized(qsym, qargs, X)
        assert (probs_q.argmax(1) == probs_f.argmax(1)).mean() > 0.93, \
            ("calib" if calib else "weight-only")


def test_multi_output_source_and_string_exclude():
    """Calibration taps resolve multi-output sources by output index,
    and a bare-string exclude= means one name, not its characters."""
    data = mx.sym.Variable("data")
    parts = mx.sym.SliceChannel(data, num_outputs=2, axis=1, name="slice")
    net = mx.sym.FullyConnected(parts[1], num_hidden=3, name="fcm")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(5)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(8, 4))[0]))
    args = {n: mx.nd.array(rng.randn(*shapes[n]).astype(np.float32))
            for n in shapes if n not in ("data", "softmax_label")}
    X = rng.randn(8, 4).astype(np.float32)
    qsym, qargs, _ = quantize_model(net, args, calib_data=[X])
    assert qargs["fcm_weight"].dtype == np.int8
    exe, probs = _run_quantized(qsym, qargs, X)
    assert probs.shape == (8, 3)

    # string exclude: the named layer must NOT be quantized
    q2, qa2, _ = quantize_model(net, args, exclude="fcm")
    assert qa2["fcm_weight"].dtype == np.float32


def test_quantized_predict_api():
    """The predict-only deployment surface consumes quantized
    artifacts unchanged (symbol JSON + int8 param blob)."""
    net, args, aux, X, y, probs_f = _trained_mlp()
    qsym, qargs, _ = quantize_model(net, args, aux)
    pred = mx.predict.create(qsym.tojson(),
                             {"arg:" + k: v for k, v in qargs.items()},
                             {"data": X.shape})
    out = np.asarray(pred.forward(data=X)[0])
    assert (out.argmax(1) == probs_f.argmax(1)).mean() > 0.98


def test_tap_resolves_ambiguous_output_names():
    """Calibration taps index internals POSITIONALLY: an RNN's
    'rnn_state' output collides with its 'rnn_state' initial-state
    variable, which a name lookup would mis-resolve; weight-only mode
    must not touch tap resolution at all."""
    data = mx.sym.Variable("data")
    rnn = mx.sym.RNN(data, state_size=8, num_layers=1, mode="lstm",
                     state_outputs=True, name="rnn")
    net = mx.sym.FullyConnected(rnn[1], num_hidden=3, name="fcs")
    net = mx.sym.SoftmaxOutput(mx.sym.Reshape(net, shape=(-1, 3)),
                               name="softmax")
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(5, 2, 4))[0]))
    rng = np.random.RandomState(6)
    args = {n: mx.nd.array(rng.randn(*shapes[n]).astype(np.float32))
            for n in shapes if n not in ("data", "softmax_label")}
    X = rng.randn(5, 2, 4).astype(np.float32)
    for calib in (None, [X]):
        qsym, qargs, _ = quantize_model(net, args, calib_data=calib)
        assert qargs["fcs_weight"].dtype == np.int8


def test_quantize_cli_tool(tmp_path):
    """tools/quantize.py round-trips a trained checkpoint to an int8
    pair loadable through the standard loaders."""
    import subprocess
    import sys as _sys

    net, args_p, aux_p, X, y, probs_f = _trained_mlp()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 3, net, args_p, aux_p)
    out = str(tmp_path / "m_int8")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "quantize.py"),
         "--prefix", prefix, "--epoch", "3", "--out", out],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-2000:]
    assert "quantized 2 layers" in r.stdout
    sym2, args2, aux2 = mx.model.load_checkpoint(out, 0)
    assert args2["fc1_weight"].dtype == np.int8
    _, probs_q = _run_quantized(sym2, args2, X)
    assert (probs_q.argmax(1) == probs_f.argmax(1)).mean() > 0.98


def test_quantize_cli_calibrated_rec(tmp_path):
    """The --calib-rec path: a RecordIO dataset drives activation
    calibration with training-matched preprocessing, and act_scale
    lands in the output symbol."""
    import json
    import subprocess
    import sys as _sys

    from mxnet_tpu import recordio

    pytest.importorskip("cv2")
    rng = np.random.RandomState(7)
    rec_path = str(tmp_path / "calib.rec")
    writer = recordio.MXRecordIO(rec_path, "w")
    for i in range(8):
        img = rng.randint(0, 255, (12, 12, 3), dtype=np.uint8)
        writer.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 2), i, 0), img, quality=95))
    writer.close()

    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             num_filter=4, pad=(1, 1), name="c1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=2,
                                name="f1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(4, 3, 12, 12))[0]))
    args_p = {n: mx.nd.array(
        np.random.RandomState(8).randn(*shapes[n]).astype(np.float32) * 0.1)
        for n in shapes if n not in ("data", "softmax_label")}
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 1, net, args_p, {})

    out = str(tmp_path / "m_int8")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "quantize.py"),
         "--prefix", prefix, "--epoch", "1", "--out", out,
         "--calib-rec", rec_path, "--batch-size", "4",
         "--data-shape", "3,12,12", "--scale", str(1.0 / 255)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, (r.stdout + "\n" + r.stderr)[-2000:]
    conf = json.loads(open(out + "-symbol.json").read())
    scales = [float(n["param"]["act_scale"]) for n in conf["nodes"]
              if n["op"].startswith("Quantized")]
    assert scales and all(s > 0 for s in scales), scales
    # preprocessing applied: calibrated input scale reflects /255 pixels
    first = min(scales)
    assert first < 1.0, scales  # raw 0-255 calibration would be >> 1
