"""Amalgamation deploy artifact (VERDICT r4 item 6): the single-file C
runtime (amalgamation/mxtpu_predict.c) runs the exported .mxa artifact
with NO Python tree, no libmxtpu, no jax — gcc + libm only — and its
outputs match the Python predictor within float tolerance.

Reference parity: amalgamation/ (predict-only single-file build,
c_predict_api.cc:1-305 consumed from one compiled object on
mobile/JS); here the artifact additionally carries StableHLO for the
jax-side loader (predict.load_exported), one export serving both."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_params(sym, input_shapes, seed):
    arg_shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    rng = np.random.RandomState(seed)
    args, aux = {}, {}
    for name, shp in zip(sym.list_arguments(), arg_shapes):
        if name in input_shapes or name.endswith("_label"):
            continue          # labels are free inputs, not parameters
        args[name] = mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.3)
    for name, shp in zip(sym.list_auxiliary_states(), aux_shapes):
        # variance-like aux must be positive
        val = (rng.rand(*shp).astype(np.float32) + 0.5
               if name.endswith("var")
               else rng.randn(*shp).astype(np.float32) * 0.1)
        aux[name] = mx.nd.array(val)
    return args, aux


def _compile_consumer(tmp_path):
    exe = str(tmp_path / "amalgamation_consumer")
    # ONLY the amalgamation pair + libm: no -lmxtpu, no Python includes
    subprocess.run(
        ["gcc", "-std=c99", "-O2", "-I" + os.path.join(REPO, "amalgamation"),
         os.path.join(REPO, "tests", "cpp", "amalgamation_consumer.c"),
         os.path.join(REPO, "amalgamation", "mxtpu_predict.c"),
         "-lm", "-o", exe],
        check=True, capture_output=True)
    return exe


def _roundtrip(tmp_path, sym, input_shape, seed, batch=None):
    """Export with random params, run the C runtime, return (c_out,
    python_out)."""
    args, aux = _random_params(sym, {"data": input_shape}, seed)
    art = str(tmp_path / f"model{seed}.mxa")
    mx.predict.export_model(art, sym, args, aux, {"data": input_shape})

    run_shape = ((batch,) + input_shape[1:]) if batch else input_shape
    rng = np.random.RandomState(seed + 1)
    x = rng.randn(*run_shape).astype(np.float32)

    in_npy = str(tmp_path / f"in{seed}.npy")
    out_npy = str(tmp_path / f"out{seed}.npy")
    np.save(in_npy, x)
    exe = _compile_consumer(tmp_path)
    env = {k: v for k, v in os.environ.items()}
    env.pop("PYTHONPATH", None)     # prove: no Python tree needed
    r = subprocess.run([exe, art, in_npy, out_npy],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    assert "AMALGAMATION_OK" in r.stdout
    c_out = np.load(out_npy)

    blob = {f"arg:{k}": v for k, v in args.items()}
    blob.update({f"aux:{k}": v for k, v in aux.items()})
    pred = mx.predict.create(sym.tojson(), blob, {"data": run_shape})
    pred.forward(data=x)
    py_out = pred.get_output(0)
    return c_out, py_out


def test_lenet_bn_artifact_matches_python(tmp_path):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(5, 5), num_filter=6, name="c1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, eps=2e-5, name="bn1")
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Convolution(net, kernel=(5, 5), num_filter=16, name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="sigmoid")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    c_out, py_out = _roundtrip(tmp_path, net, (2, 1, 28, 28), seed=3)
    assert c_out.shape == py_out.shape
    np.testing.assert_allclose(c_out, py_out, atol=1e-5, rtol=1e-4)


def test_resnet_block_artifact_matches_python(tmp_path):
    """Residual topology: conv+bn trunk with an elementwise shortcut and
    global average pooling — the ResNet op family end to end."""
    data = mx.sym.Variable("data")
    trunk = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                               num_filter=8, no_bias=True, name="c1")
    trunk = mx.sym.BatchNorm(trunk, fix_gamma=False, name="bn1")
    trunk = mx.sym.Activation(trunk, act_type="relu")
    trunk = mx.sym.Convolution(trunk, kernel=(3, 3), pad=(1, 1),
                               num_filter=8, no_bias=True, name="c2")
    short = mx.sym.Convolution(data, kernel=(1, 1), num_filter=8,
                               no_bias=True, name="sc")
    net = trunk + short
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    c_out, py_out = _roundtrip(tmp_path, net, (2, 3, 16, 16), seed=5)
    np.testing.assert_allclose(c_out, py_out, atol=1e-5, rtol=1e-4)


def test_artifact_batch_flexibility(tmp_path):
    """The C runtime re-infers shapes from the fed batch: export at
    batch 1, run at batch 4 (deploy-time batching)."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    c_out, py_out = _roundtrip(tmp_path, net, (1, 12), seed=7, batch=4)
    assert c_out.shape == (4, 4)
    np.testing.assert_allclose(c_out, py_out, atol=1e-5, rtol=1e-4)


def test_one_command_export_cli(tmp_path):
    """tools/export_model.py: checkpoint prefix -> .mxa in one command;
    the SAME artifact then loads through the jax-side ExportedPredictor
    (two consumers, one export)."""
    net = mx.models.mlp(num_classes=5)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 20))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(initializer=mx.init.Xavier())
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 0)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"         # the test tier's pinned backend
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "export_model.py"),
         "--prefix", prefix, "--epoch", "0", "--data-shape", "2,20"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    art = prefix + ".mxa"
    assert os.path.exists(art)

    # jax-side consumer of the same artifact
    pred = mx.predict.load_exported(art)
    x = np.random.RandomState(0).randn(2, 20).astype(np.float32)
    pred.forward(data=x)
    out = pred.get_output(0)
    assert out.shape == (2, 5)

    # C-side consumer of the same artifact
    exe = _compile_consumer(tmp_path)
    in_npy, out_npy = str(tmp_path / "x.npy"), str(tmp_path / "y.npy")
    np.save(in_npy, x)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([exe, art, in_npy, out_npy], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    np.testing.assert_allclose(np.load(out_npy), np.asarray(out),
                               atol=1e-5, rtol=1e-4)


def test_unsupported_op_fails_loudly(tmp_path):
    data = mx.sym.Variable("data")
    net = mx.sym.SwapAxis(data, dim1=0, dim2=1)
    art = str(tmp_path / "bad.mxa")
    mx.predict.export_model(art, net, {}, {}, {"data": (2, 3)})
    exe = _compile_consumer(tmp_path)
    in_npy = str(tmp_path / "x.npy")
    np.save(in_npy, np.zeros((2, 3), np.float32))
    r = subprocess.run([exe, art, in_npy, str(tmp_path / "y.npy")],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "unsupported op" in (r.stdout + r.stderr)


@pytest.mark.slow
def test_corrupt_artifact_never_crashes(tmp_path):
    """Byte-level robustness: random truncations and single-byte
    corruptions of a valid artifact must produce clean errors (rc=1),
    never signals — the parser-hardening contract, fuzz-style."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(11)
    args = {"fc_weight": mx.nd.array(rng.randn(3, 4).astype(np.float32)),
            "fc_bias": mx.nd.array(rng.randn(3).astype(np.float32))}
    art = str(tmp_path / "m.mxa")
    mx.predict.export_model(art, net, args, {}, {"data": (1, 4)})
    blob = bytearray(open(art, "rb").read())
    exe = _compile_consumer(tmp_path)
    in_npy = str(tmp_path / "x.npy")
    np.save(in_npy, np.zeros((1, 4), np.float32))

    def run(payload):
        bad = str(tmp_path / "bad.mxa")
        open(bad, "wb").write(bytes(payload))
        # bytes mode: corrupt entry names can echo into stderr as
        # non-UTF-8 via the runtime's error messages
        r = subprocess.run([exe, bad, in_npy, str(tmp_path / "y.npy")],
                           capture_output=True, timeout=60)
        # clean outcome only: success or a clean error exit — a signal
        # (negative returncode) means the parsers read out of bounds
        assert r.returncode in (0, 1), (
            r.returncode, r.stderr[-300:].decode("utf-8", "replace"))

    for cut in (0, 10, 22, len(blob) // 4, len(blob) // 2, len(blob) - 3):
        run(blob[:cut])                       # truncations
    for _ in range(60):                       # single-byte corruptions
        mutated = bytearray(blob)
        pos = rng.randint(0, len(mutated))
        mutated[pos] = rng.randint(0, 256)
        run(mutated)
