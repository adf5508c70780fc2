#!/usr/bin/env python
"""Int8 quantized-inference benchmark: ResNet-50 batch inference in
float (bf16 on TPU) vs weight-only int8 vs calibrated full-int8.

Reports images/sec for each mode plus the speedups — the measurement
behind contrib/quantization.py's claims (4x smaller weight reads;
int8 x int8 -> int32 MXU contractions at double int8 throughput on
v5e+).

Usage: python tools/quant_bench.py [--batch 256] [--json OUT]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def bench_forward(exe, data, n_warmup, n_iter):
    import jax

    exe.arg_dict["data"][:] = data
    for _ in range(n_warmup):
        outs = exe.forward(is_train=False)
    jax.block_until_ready([o._data for o in outs])
    tic = time.perf_counter()
    # keep EVERY call's outputs and block on all of them: the remote
    # runtime executes independent dispatches out of order, so blocking
    # only on the last call's buffers would not wait for the other
    # n_iter - 1 (pipelined throughput is the honest serving number,
    # but only once every inference actually finished)
    all_outs = []
    for _ in range(n_iter):
        all_outs.append([o._data for o in exe.forward(is_train=False)])
    jax.block_until_ready(all_outs)
    return time.perf_counter() - tic


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--json", default=None,
                   help="append the result as one JSON line to this file")
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.contrib.quantization import quantize_model

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        batch = args.batch or 256
        hw, n_warmup, n_iter = 224, 3, 15
    else:  # smoke shapes
        batch = args.batch or 8
        hw, n_warmup, n_iter = 32, 1, 3

    net = mx.models.resnet(num_classes=1000, num_layers=50,
                           image_shape=(3, hw, hw),
                           layout="NHWC" if on_tpu else "NCHW",
                           stem="conv7")
    data_shape = ((batch, hw, hw, 3) if on_tpu else (batch, 3, hw, hw))

    rng = np.random.RandomState(0)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=data_shape)[0]))
    arg_params = {}
    for n, s in shapes.items():
        if n in ("data", "softmax_label"):
            continue
        arg_params[n] = mx.nd.array(
            rng.standard_normal(s).astype(np.float32) * 0.05)
    aux_names = net.list_auxiliary_states()
    aux_shapes = dict(zip(aux_names, net.infer_shape(data=data_shape)[2]))
    aux_params = {n: mx.nd.array(
        np.ones(aux_shapes[n], np.float32) if n.endswith("var")
        else np.zeros(aux_shapes[n], np.float32)) for n in aux_names}

    data = rng.uniform(-1, 1, data_shape).astype(np.float32)

    def run(sym, params, tag):
        exe = sym.simple_bind(mx.tpu(0) if on_tpu else mx.cpu(),
                              grad_req="null", data=data_shape,
                              softmax_label=(batch,))
        for k, v in params.items():
            if k in exe.arg_dict:
                exe.arg_dict[k][:] = v
        for k, v in aux_params.items():
            if k in exe.aux_dict:
                exe.aux_dict[k][:] = v
        dt = bench_forward(exe, data, n_warmup, n_iter)
        ips = batch * n_iter / dt
        print(f"{tag}: {ips:.1f} img/s")
        return ips

    result = {"metric": "resnet50_int8_inference",
              "batch": batch, "image_hw": hw,
              "platform": jax.default_backend(),
              "device_kind": getattr(jax.devices()[0], "device_kind", "")}
    result["float_img_per_sec"] = round(run(net, arg_params, "float"), 1)

    qsym_wo, qargs_wo, _ = quantize_model(net, arg_params, aux_params,
                                          exclude=("conv0",))
    result["weight_only_img_per_sec"] = round(
        run(qsym_wo, qargs_wo, "weight-only int8"), 1)

    qsym_i8, qargs_i8, _ = quantize_model(net, arg_params, aux_params,
                                          calib_data=[data[: max(batch // 4,
                                                                 1)]],
                                          num_calib_batches=1,
                                          exclude=("conv0",))
    result["int8_img_per_sec"] = round(run(qsym_i8, qargs_i8, "full int8"),
                                       1)

    f = result["float_img_per_sec"]
    result["weight_only_speedup"] = round(
        result["weight_only_img_per_sec"] / f, 3)
    result["int8_speedup"] = round(result["int8_img_per_sec"] / f, 3)
    # explicit completeness contract: a stamped complete=true marks
    # this single-shot payload as a full capture (all three modes
    # measured)
    result["complete"] = True
    print(json.dumps(result))
    if args.json:
        with open(args.json, "a") as fh:
            fh.write(json.dumps(result) + "\n")


if __name__ == "__main__":
    main()
