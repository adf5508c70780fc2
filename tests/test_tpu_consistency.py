"""TPU-vs-CPU consistency (rebuild of tests/python/gpu/test_operator_gpu.py:
run the same symbols on both backends and compare forward/backward within
dtype tolerances).

The suite itself is pinned to the CPU (conftest.py) and must not hold the
chip, so each platform's cases run in ONE child process: a CPU worker for
the reference, and a worker on the machine's default backend — which must
be a TPU.  Gated behind MXTPU_TPU_TESTS=1 (the cases pay first-compile
latency and need the chip); with the gate set, an absent chip FAILS.

Run: MXTPU_TPU_TESTS=1 python -m pytest tests/test_tpu_consistency.py -q
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    os.environ.get("MXTPU_TPU_TESTS") != "1",
    reason="TPU consistency tests gated behind MXTPU_TPU_TESTS=1")

_WORKER = r"""
import json, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
# full f32 matmul/conv precision: the default bf16 MXU passes are fine
# for training but flip ReLU boundaries, which makes gradient comparison
# against CPU meaningless at those elements
jax.config.update("jax_default_matmul_precision", "highest")
import mxnet_tpu as mx

if %(tpu)s and jax.devices()[0].platform != "tpu":
    sys.exit("no TPU: jax reports platform %%r" %% jax.devices()[0].platform)

cases = {}

def case(name):
    def deco(fn):
        cases[name] = fn
        return fn
    return deco

@case("conv_bn_relu")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                             name="c")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn")
    net = mx.sym.Activation(net, act_type="relu")
    return net, {"data": (4, 3, 8, 8)}, {"bn_moving_var": 1.0}

@case("fc_softmax")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax"), \
        {"data": (8, 12), "softmax_label": (8,)}, {}

@case("pool_flatten_dot")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.Pooling(data, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net)
    return net, {"data": (4, 2, 6, 6)}, {}

@case("rnn_lstm")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.RNN(data, state_size=8, num_layers=1, mode="lstm",
                     name="rnn")
    return net, {"data": (5, 2, 4)}, {}

@case("flash_attention_causal")
def _():
    # real Pallas kernel on TPU vs the interpreter on CPU, including the
    # causal block-skip path
    q = mx.sym.Variable("q")
    k = mx.sym.Variable("k")
    v = mx.sym.Variable("v")
    net = mx.sym.FlashAttention(q, k, v, causal=True)
    shp = (2, 2, 16, 8)
    return net, {"q": shp, "k": shp, "v": shp}, {}

@case("flash_attention_window_gqa")
def _():
    # sliding-window band + grouped-query (bshd native) composed, at a
    # shape the auto path gives the kernel (two 128-row blocks per axis:
    # the 8-row blocks this case used to ask for are not Mosaic-legal, so
    # impl="auto" would hand the TPU side to dense XLA without a word)
    q = mx.sym.Variable("q")
    k = mx.sym.Variable("k")
    v = mx.sym.Variable("v")
    net = mx.sym.FlashAttention(q, k, v, causal=True, layout="bshd",
                                window=64, block_q=128, block_k=128)
    return net, {"q": (2, 256, 4, 8), "k": (2, 256, 2, 8),
                 "v": (2, 256, 2, 8)}, {}

@case("rope_gpt_block")
def _():
    # RoPE rotation feeding fused attention (rope is elementwise XLA,
    # but its trig must agree cross-platform through the kernel)
    q = mx.sym.Variable("q")
    k = mx.sym.Variable("k")
    v = mx.sym.Variable("v")
    net = mx.sym.FlashAttention(mx.sym.RoPE(q, layout="bshd"),
                                mx.sym.RoPE(k, layout="bshd"), v,
                                causal=True, layout="bshd",
                                block_q=128, block_k=128)
    shp = (2, 256, 2, 8)
    return net, {"q": shp, "k": shp, "v": shp}, {}

@case("llama_gpt_step")
def _():
    # the whole round-4 stack in one case: rmsnorm + swiglu + rope +
    # tied embeddings + GQA + windowed flash attention + fused CE head
    net = mx.models.gpt(13, 8, num_layers=1, d_model=16, num_heads=2,
                        kv_heads=1, attn_window=4, pos_embed="rope",
                        norm="rmsnorm", mlp="swiglu", tie_embeddings=True,
                        loss="ce")
    return net, {"data": (2, 8), "softmax_label": (2, 8)}, {}, {
        "data": lambda rng, shape: rng.randint(0, 13, shape)
        .astype(np.float32),
        "softmax_label": lambda rng, shape: rng.randint(0, 13, shape)
        .astype(np.float32)}

@case("layernorm_gelu")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.LayerNorm(data, name="ln")
    net = mx.sym.gelu(net)
    return net, {"data": (4, 32)}, {}

@case("rnn_lstm_pallas")
def _():
    # H=128 / N=8 / T>=8 meets the Mosaic eligibility gate
    # (ops/pallas_lstm.py fused_lstm_eligible), so on TPU this runs the
    # REAL fused Pallas kernel while the CPU side runs the lax.scan
    # cell — a genuine cross-implementation consistency check.
    # Weights get a 1/sqrt(H)-class init: at H=128 an N(0,1) recurrent
    # matrix saturates the gates and makes backward chaotic, so ANY two
    # correct implementations (even TPU scan vs CPU scan) disagree
    # wildly; on-chip fused-vs-scan agreement is separately pinned to
    # ~1e-6 by test_perf_contract's interpret parity plus this case
    data = mx.sym.Variable("data")
    net = mx.sym.RNN(data, state_size=128, num_layers=1, mode="lstm",
                     name="rnnp")
    return net, {"data": (8, 8, 16)}, {}, {
        "rnnp_parameters": lambda rng, shape: rng.normal(
            0, 0.08, shape).astype(np.float32)}

@case("rnn_gru_pallas")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.RNN(data, state_size=128, num_layers=1, mode="gru",
                     name="rnng")
    return net, {"data": (8, 8, 16)}, {}, {
        "rnng_parameters": lambda rng, shape: rng.normal(
            0, 0.08, shape).astype(np.float32)}

@case("deconv")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.Deconvolution(data, kernel=(4, 4), stride=(2, 2),
                               pad=(1, 1), num_filter=6, name="dc")
    return net, {"data": (2, 3, 7, 7)}, {}

@case("lrn_leaky")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.LRN(data, nsize=3, alpha=1e-4, beta=0.75, knorm=2.0)
    net = mx.sym.LeakyReLU(net, act_type="leaky", slope=0.1)
    return net, {"data": (2, 8, 6, 6)}, {}

@case("softmax_activation_channel")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxActivation(data, mode="channel")
    return net, {"data": (2, 5, 4, 4)}, {}

@case("upsampling_bilinear")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.UpSampling(data, scale=2, sample_type="bilinear",
                            num_filter=4, name="up")
    return net, {"data": (2, 4, 5, 5)}, {}

@case("spatial_transformer")
def _():
    data = mx.sym.Variable("data")
    loc = mx.sym.Variable("loc")
    net = mx.sym.SpatialTransformer(
        data, loc, target_shape=(6, 6), transform_type="affine",
        sampler_type="bilinear", name="st")
    return net, {"data": (2, 3, 8, 8), "loc": (2, 6)}, {}, {
        # near-identity affine params keep the sample grid in-bounds
        "loc": lambda rng, shape: (np.tile(
            np.array([1, 0, 0, 0, 1, 0], np.float32), (2, 1))
            + rng.normal(0, 0.05, (2, 6)).astype(np.float32))}

@case("roi_pooling")
def _():
    data = mx.sym.Variable("data")
    rois = mx.sym.Variable("rois")
    net = mx.sym.ROIPooling(data, rois, pooled_size=(3, 3),
                            spatial_scale=1.0, name="roi")
    return net, {"data": (1, 4, 10, 10), "rois": (3, 5)}, {}, {
        "rois": lambda rng, shape: np.array(
            [[0, 1, 1, 7, 7], [0, 0, 0, 9, 9], [0, 2, 3, 6, 8]],
            np.float32)}

@case("correlation")
def _():
    a = mx.sym.Variable("data1")
    b = mx.sym.Variable("data2")
    net = mx.sym.Correlation(a, b, kernel_size=1, max_displacement=2,
                             stride1=1, stride2=1, pad_size=2)
    return net, {"data1": (1, 3, 8, 8), "data2": (1, 3, 8, 8)}, {}

@case("instance_l2norm")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.InstanceNorm(data, name="in")
    net = mx.sym.L2Normalization(net, mode="instance")
    return net, {"data": (3, 4, 5, 5)}, {}

@case("concat_slice_swap")
def _():
    a = mx.sym.Variable("data1")
    b = mx.sym.Variable("data2")
    net = mx.sym.Concat(a, b, dim=1)
    net = mx.sym.SwapAxis(net, dim1=1, dim2=2)
    parts = mx.sym.SliceChannel(net, num_outputs=2, axis=2)
    return parts[0] + parts[1], {"data1": (2, 3, 6), "data2": (2, 3, 6)}, {}

@case("pad_crop_pool_avg")
def _():
    data = mx.sym.Variable("data")
    net = mx.sym.Pad(data, mode="edge", pad_width=(0, 0, 0, 0, 1, 1, 1, 1))
    net = mx.sym.Crop(net, offset=(1, 1), h_w=(6, 6))
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    return net, {"data": (2, 3, 6, 6)}, {}

@case("sequence_mask_reverse_last")
def _():
    data = mx.sym.Variable("data")
    lengths = mx.sym.Variable("len")
    net = mx.sym.SequenceMask(data, use_sequence_length=True,
                              sequence_length=lengths, value=0.0)
    net = mx.sym.SequenceReverse(net, use_sequence_length=True,
                                 sequence_length=lengths)
    net = mx.sym.SequenceLast(net, use_sequence_length=True,
                              sequence_length=lengths)
    return net, {"data": (6, 3, 4), "len": (3,)}, {}, {
        "len": lambda rng, shape: np.array([2, 6, 4], np.float32)}

@case("dropout_rng_invariance")
def _():
    # threefry is bit-identical across backends: the SAME mx seed must
    # produce the SAME dropout mask on CPU and TPU, making even a
    # stochastic op cross-platform comparable
    data = mx.sym.Variable("data")
    net = mx.sym.Dropout(data, p=0.4)
    return net * 3.0, {"data": (16, 32)}, {}

@case("embedding_gather_scatter")
def _():
    idx = mx.sym.Variable("idx")
    emb = mx.sym.Embedding(idx, input_dim=11, output_dim=6, name="emb")
    return mx.sym.sum(emb, axis=(1,)), {"idx": (4, 5)}, {}, {
        "idx": lambda rng, shape: rng.randint(0, 11, shape).astype(np.float32)}

def run_case(name):
    spec = cases[name]()
    sym, shapes, aux_init = spec[0], spec[1], spec[2]
    arg_init = spec[3] if len(spec) > 3 else {}
    rng = np.random.RandomState(0)
    mx.random.seed(0)  # RNG ops (dropout) draw identical keys on both sides
    exe = sym.simple_bind(mx.tpu(0) if %(tpu)s else mx.cpu(0),
                          grad_req="write", **shapes)
    for k, v in exe.arg_dict.items():
        if k in arg_init:
            v[:] = arg_init[k](rng, v.shape)
        else:
            v[:] = rng.normal(0, 1, v.shape)
    for k, v in exe.aux_dict.items():
        v[:] = aux_init.get(k, 0.0)
    outs = exe.forward(is_train=True)
    exe.backward([mx.nd.ones(o.shape) for o in outs])
    return {"outs": [np.asarray(o.asnumpy(), np.float64).tolist()
                     for o in outs],
            "grads": {k: np.asarray(g.asnumpy(), np.float64).tolist()
                      for k, g in exe.grad_dict.items() if g is not None}}


# one worker runs the WHOLE batch: jax import + backend init are paid
# once per platform instead of once per case, and each finished case is
# flushed immediately so a crash loses only the in-flight case
import traceback

for _name in sys.argv[1].split(","):
    print("CASE " + _name, flush=True)
    try:
        _res = run_case(_name)
    except Exception:
        _res = {"error": traceback.format_exc()[-2000:]}
    print("RESULT " + json.dumps({_name: _res}), flush=True)
print("BATCH_DONE", flush=True)
"""


CASES = ["conv_bn_relu", "fc_softmax",
         "pool_flatten_dot", "rnn_lstm",
         "flash_attention_causal",
         "flash_attention_window_gqa",
         "rope_gpt_block",
         "llama_gpt_step",
         "layernorm_gelu",
         "rnn_lstm_pallas", "rnn_gru_pallas",
         "deconv", "lrn_leaky",
         "softmax_activation_channel",
         "upsampling_bilinear",
         "spatial_transformer", "roi_pooling",
         "correlation", "instance_l2norm",
         "concat_slice_swap",
         "pad_crop_pool_avg",
         "sequence_mask_reverse_last",
         "dropout_rng_invariance",
         "embedding_gather_scatter"]

# one worker per platform, results cached for every test
_RESULTS = {}


def _run_worker(tpu):
    """Run every case on one platform; returns {case: payload}, where a
    payload is the outputs/grads dict or {"error": traceback}.  A worker
    that dies takes the unfinished cases with it: they fail, with its
    stderr."""
    env = dict(os.environ)
    if tpu:
        # conftest pins the pytest process to the CPU; the TPU worker
        # must not inherit that or it compares CPU against CPU vacuously
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = "cpu"
    src = _WORKER % {"repo": REPO, "tpu": "True" if tpu else "False"}
    r = subprocess.run([sys.executable, "-c", src, ",".join(CASES)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300 + 60 * len(CASES))
    results = {}
    for ln in r.stdout.splitlines():
        if ln.startswith("RESULT "):
            results.update(json.loads(ln[len("RESULT "):]))
    for case in CASES:
        results.setdefault(case, {
            "error": f"worker exited {r.returncode} before this case "
                     f"finished:\n{r.stderr[-1500:]}"})
    return results


def _get_results(tpu):
    key = "tpu" if tpu else "cpu"
    if key not in _RESULTS:
        _RESULTS[key] = _run_worker(tpu)
    return _RESULTS[key]


@pytest.mark.parametrize("case", CASES)
def test_tpu_matches_cpu(case):
    cpu = _get_results(tpu=False)[case]
    assert "error" not in cpu, f"CPU reference failed:\n{cpu['error']}"
    tpu = _get_results(tpu=True)[case]
    assert "error" not in tpu, f"TPU case failed:\n{tpu['error']}"
    # The fused recurrent kernels compare DIFFERENT implementations
    # (Pallas kernel on the TPU VPU vs lax.scan on CPU): per-step
    # sigmoid/tanh approximation differences (~1e-3 in the output) feed
    # back through the recurrence for T steps, so forward gets the same
    # order-looser tolerance backward always had.
    fwd_rtol, fwd_atol = ((1e-2, 5e-3)
                          if case in ("rnn_lstm_pallas", "rnn_gru_pallas")
                          else (2e-3, 1e-3))
    for o_t, o_c in zip(tpu["outs"], cpu["outs"]):
        np.testing.assert_allclose(np.array(o_t), np.array(o_c),
                                   rtol=fwd_rtol, atol=fwd_atol)
    for k in cpu["grads"]:
        # backward through batch statistics cancels catastrophically;
        # keep gradient tolerance an order looser than forward
        np.testing.assert_allclose(np.array(tpu["grads"][k]),
                                   np.array(cpu["grads"][k]),
                                   rtol=1e-2, atol=5e-3,
                                   err_msg=f"{case}:{k}")
