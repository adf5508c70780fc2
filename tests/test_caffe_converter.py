"""caffemodel binary import (tools/caffe_converter parity): the pure-
python protobuf wire reader + blob->parameter mapping, verified against
a hand-encoded NetParameter binary."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.caffe import (convert_model, load_caffemodel_params,
                             parse_caffemodel)

rng = np.random.RandomState(5)


# ------------------------------------------------- protobuf wire encoder
def _varint(v):
    out = b""
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _tag(fnum, wtype):
    return _varint((fnum << 3) | wtype)


def _len_field(fnum, payload):
    return _tag(fnum, 2) + _varint(len(payload)) + payload


def _blob(arr, legacy4d=False):
    arr = np.asarray(arr, np.float32)
    msg = b""
    if legacy4d:
        shape = (1,) * (4 - arr.ndim) + arr.shape
        for fnum, d in zip((1, 2, 3, 4), shape):
            msg += _tag(fnum, 0) + _varint(d)
    else:
        msg += _len_field(7, _pack_shape(arr.shape))
    msg += _len_field(5, arr.tobytes())  # packed float data
    return msg


def _pack_shape(shape):
    # BlobShape { repeated int64 dim = 1 [packed] }
    dims = b"".join(_varint(d) for d in shape)
    return _len_field(1, dims)


def _layer(name, ltype, blobs, v1=False):
    if v1:
        msg = _len_field(4, name.encode())
        msg += _tag(5, 0) + _varint(4)  # enum CONVOLUTION
        for b in blobs:
            msg += _len_field(6, _blob(b, legacy4d=True))
        return _len_field(2, msg)
    msg = _len_field(1, name.encode()) + _len_field(2, ltype.encode())
    for b in blobs:
        msg += _len_field(7, _blob(b))
    return _len_field(100, msg)


PROTOTXT = """
name: "tiny"
layer { name: "data" type: "Input" top: "data" }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1 } }
layer { name: "bn1" type: "BatchNorm" bottom: "conv1" top: "bn1" }
layer { name: "scale1" type: "Scale" bottom: "bn1" top: "bn1"
  scale_param { bias_term: true } }
layer { name: "relu1" type: "ReLU" bottom: "bn1" top: "bn1" }
layer { name: "fc1" type: "InnerProduct" bottom: "bn1" top: "fc1"
  inner_product_param { num_output: 3 } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc1" bottom: "label" }
"""


def _make_caffemodel():
    w_conv = rng.randn(4, 2, 3, 3).astype(np.float32)
    b_conv = rng.randn(4).astype(np.float32)
    bn_mean = rng.randn(4).astype(np.float32)
    bn_var = rng.rand(4).astype(np.float32) + 0.5
    sf = np.array([2.0], np.float32)  # scale factor: stored = 2*true
    gamma = rng.rand(4).astype(np.float32) + 0.5
    beta = rng.randn(4).astype(np.float32)
    w_fc = rng.randn(3, 4 * 8 * 8).astype(np.float32)
    b_fc = rng.randn(3).astype(np.float32)
    net = (_layer("conv1", "Convolution", [w_conv, b_conv])
           + _layer("bn1", "BatchNorm", [bn_mean * 2, bn_var * 2, sf])
           + _layer("scale1", "Scale", [gamma, beta])
           + _layer("fc1", "InnerProduct", [w_fc, b_fc]))
    weights = dict(w_conv=w_conv, b_conv=b_conv, bn_mean=bn_mean,
                   bn_var=bn_var, gamma=gamma, beta=beta, w_fc=w_fc,
                   b_fc=b_fc)
    return net, weights


def test_parse_caffemodel_blobs():
    net, w = _make_caffemodel()
    layers = parse_caffemodel(net)
    names = [n for n, _ in layers]
    assert names == ["conv1", "bn1", "scale1", "fc1"]
    blobs = dict(layers)
    np.testing.assert_allclose(blobs["conv1"][0], w["w_conv"])
    assert blobs["conv1"][0].shape == (4, 2, 3, 3)
    np.testing.assert_allclose(blobs["fc1"][1], w["b_fc"])


def test_parse_caffemodel_v1_layers():
    arr = rng.randn(2, 3).astype(np.float32)
    bias = rng.randn(2).astype(np.float32)
    net = _layer("old_conv", "", [arr, bias], v1=True)
    layers = parse_caffemodel(net)
    assert layers[0][0] == "old_conv"
    # legacy num/channels/height/width shape: (1,1,2,3) squeezed of
    # leading ones is not applied — raw 4d kept
    assert layers[0][1][0].reshape(2, 3).shape == (2, 3)
    np.testing.assert_allclose(layers[0][1][0].reshape(2, 3), arr)


def test_load_caffemodel_params_mapping():
    net, w = _make_caffemodel()
    args, aux = load_caffemodel_params(PROTOTXT, net)
    np.testing.assert_allclose(args["conv1_weight"], w["w_conv"])
    np.testing.assert_allclose(args["conv1_bias"], w["b_conv"])
    # scale-factor normalization: stored mean/var divided by sf
    np.testing.assert_allclose(aux["bn1_moving_mean"], w["bn_mean"],
                               rtol=1e-6)
    np.testing.assert_allclose(aux["bn1_moving_var"], w["bn_var"],
                               rtol=1e-6)
    # Scale folds onto the BatchNorm's gamma/beta
    np.testing.assert_allclose(args["bn1_gamma"], w["gamma"])
    np.testing.assert_allclose(args["bn1_beta"], w["beta"])
    np.testing.assert_allclose(args["fc1_weight"], w["w_fc"])


def test_convert_model_runs_forward():
    net, w = _make_caffemodel()
    symbol, arg_params, aux_params = convert_model(PROTOTXT, net)
    x = rng.randn(2, 2, 8, 8).astype(np.float32)
    exe = symbol.simple_bind(mx.cpu(), grad_req="null", data=x.shape,
                             softmax_label=(2,))
    exe.arg_dict["data"][:] = x
    for k, v in arg_params.items():
        exe.arg_dict[k][:] = v
    for k, v in aux_params.items():
        exe.aux_dict[k][:] = v
    out = exe.forward(is_train=False)[0].asnumpy()
    assert out.shape == (2, 3)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)


@pytest.mark.slow
def test_cli_roundtrip(tmp_path):
    net, _ = _make_caffemodel()
    pt = tmp_path / "deploy.prototxt"
    cm = tmp_path / "net.caffemodel"
    pt.write_text(PROTOTXT)
    cm.write_bytes(net)
    prefix = str(tmp_path / "imported")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "caffe_converter.py")
    r = subprocess.run([sys.executable, tool, str(pt), str(cm), prefix],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    sym, args, aux = mx.model.load_checkpoint(prefix, 0)
    assert "conv1_weight" in args and "bn1_moving_mean" in aux


def test_v1_legacy_innerproduct_weight_reshaped():
    # V1 blobs have legacy (1,1,out,in) shapes; the mapper must deliver
    # a bindable 2-d FC weight
    w = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    proto = """
layer { name: "data" type: "Input" top: "data" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 3 } }
"""
    msg = (_len_field(1, b"ip") + _len_field(2, b"InnerProduct")
           + _len_field(7, _blob(w, legacy4d=True))
           + _len_field(7, _blob(b, legacy4d=True)))
    net = _len_field(100, msg)
    args, _ = load_caffemodel_params(proto, net)
    assert args["ip_weight"].shape == (3, 4)
    np.testing.assert_allclose(args["ip_weight"], w)
    assert args["ip_bias"].shape == (3,)


def test_truncated_caffemodel_rejected():
    net, _ = _make_caffemodel()
    with pytest.raises(MXNetError):
        parse_caffemodel(net[:-20])
    # truncation inside a varint (continuation bit set at EOF)
    with pytest.raises(MXNetError):
        parse_caffemodel(b"\x82\x86")


def test_load_mean_binaryproto():
    from mxnet_tpu.caffe import load_mean_binaryproto
    mean = rng.rand(3, 6, 5).astype(np.float32)
    blob = _blob(mean, legacy4d=True)  # (1, 3, 6, 5) legacy shape
    out = load_mean_binaryproto(blob)
    assert out.shape == (3, 6, 5)
    np.testing.assert_allclose(out, mean, rtol=1e-6)
    blob2 = _blob(mean)                # BlobShape form
    np.testing.assert_allclose(load_mean_binaryproto(blob2), mean)
