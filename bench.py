#!/usr/bin/env python
"""Benchmark harness: ResNet-50 training throughput on the local chip(s).

Prints ONE JSON line:
  {"metric": "resnet50_train_throughput", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N}

Baseline: BASELINE.md's north star is ">= A100-class img/sec/chip" for
ResNet-50 ImageNet training; A100 mixed-precision ResNet-50 training
is ~2500 img/s/chip (MLPerf-era public number), so vs_baseline =
value / 2500.  Data is synthetic device-resident (the harness measures
the compute path, like the reference's benchmark.py synthetic mode —
example/image-classification/benchmark.py).

One process, one chip: the benchmark runs in the process that holds the
device.  Without a TPU it exits non-zero — unless ``JAX_PLATFORMS=cpu``
was set explicitly, which runs tiny shapes and stamps ``platform: cpu``
(the contract tests' mode; never a device number).
"""

import json
import os
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0


def main():
    import jax
    import numpy as np

    import mxnet_tpu as mx

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"bench.py: no TPU (jax reports platform {platform!r}); "
                 "set JAX_PLATFORMS=cpu explicitly for the tiny-shape "
                 "contract mode")
    n_chips = len(jax.devices())

    if os.environ.get("BENCH_MODEL", "resnet50") == "gpt":
        return bench_gpt(jax, np, mx, on_tpu, n_chips)
    if os.environ.get("BENCH_MODEL") == "cifar":
        return bench_cifar(jax, np, mx, on_tpu, n_chips)

    if on_tpu:
        batch_per_chip = int(os.environ.get("BENCH_BATCH", "128"))
        image_hw = 224
        dtype = "bfloat16"
        n_warmup, n_iter = 5, 20
    else:  # CPU smoke mode: tiny shapes so the harness itself is testable
        batch_per_chip = 8
        image_hw = 32
        dtype = "float32"
        n_warmup, n_iter = 2, 5

    batch = batch_per_chip * n_chips
    layout = os.environ.get("BENCH_LAYOUT", "NHWC" if on_tpu else "NCHW")
    # space-to-depth stem (input pre-transformed to H/2 x W/2 x 4C) keeps
    # the stem conv dense on the MXU; standard for TPU ResNet training
    stem = os.environ.get(
        "BENCH_STEM", "s2d" if on_tpu and layout == "NHWC" else "conv7")
    net = mx.models.resnet(num_classes=1000, num_layers=50,
                           image_shape=(3, image_hw, image_hw), layout=layout,
                           stem=stem)
    if stem == "s2d":
        data_shape = (batch, image_hw // 2, image_hw // 2, 12)
    elif layout == "NHWC":
        data_shape = (batch, image_hw, image_hw, 3)
    else:
        data_shape = (batch, 3, image_hw, image_hw)

    _train_throughput(
        jax, np, mx, net,
        input_shapes={"data": data_shape, "softmax_label": (batch,)},
        label_classes=1000, dtype=dtype, n_warmup=n_warmup, n_iter=n_iter,
        on_tpu=on_tpu, n_chips=n_chips,
        metric="resnet50_train_throughput", unit="images/sec/chip",
        per_chip_divisor=batch, baseline=BASELINE_IMG_PER_SEC_PER_CHIP,
        extra_fields={"batch_per_chip": batch_per_chip,
                      "image_hw": image_hw, "layout": layout,
                      "stem": stem},
        a100_baseline=True)


def _train_throughput(jax, np, mx, net, input_shapes, label_classes, dtype,
                      n_warmup, n_iter, on_tpu, n_chips, metric, unit,
                      per_chip_divisor, baseline, extra_fields,
                      a100_baseline=False, optimizer="sgd",
                      optimizer_params=None, initializer=None,
                      input_dtypes=None):
    """Shared body of every bench mode: build a dp ShardedTrainer over
    ``net``, place one synthetic device-resident batch, run the
    warmup+timed loop, and print the one-JSON-line result (throughput =
    per_chip_divisor * n_iter / dt / n_chips, in ``unit``)."""
    data_shape = input_shapes["data"]
    batch = data_shape[0]
    optimizer_params = dict(optimizer_params
                            or {"learning_rate": 0.1, "momentum": 0.9})
    # sweepable optimizer-state dtype (momentum buffer storage): default
    # follows param dtype (bf16 under BENCH -> half the optimizer HBM
    # traffic); BENCH_OPT_STATE_DTYPE=float32 measures full-precision
    # accumulation
    opt_state_dtype = os.environ.get("BENCH_OPT_STATE_DTYPE")
    if opt_state_dtype and optimizer == "sgd":
        optimizer_params["state_dtype"] = opt_state_dtype
    trainer = mx.parallel.ShardedTrainer(
        net, input_shapes,
        mesh=mx.parallel.local_mesh("dp"),
        optimizer=optimizer,
        optimizer_params=optimizer_params,
        initializer=(initializer
                     or mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2)),
        dtype=dtype, input_dtypes=input_dtypes)
    rng = np.random.RandomState(0)
    if input_dtypes and np.issubdtype(input_dtypes.get("data"), np.integer):
        data = rng.randint(0, label_classes, data_shape)
    else:
        data = rng.uniform(-1, 1, data_shape).astype(np.float32)
    label = rng.randint(0, label_classes,
                        input_shapes["softmax_label"]).astype(
        input_dtypes.get("softmax_label", np.float32) if input_dtypes
        else np.float32)
    # place once; reuse device-resident batch (synthetic-data mode)
    placed = trainer._place_batch({"data": data, "softmax_label": label})

    dt = _timed_steps(jax, trainer, placed, n_warmup, n_iter)

    value_per_chip = per_chip_divisor * n_iter / dt / n_chips
    result = {
        "metric": metric,
        "value": round(value_per_chip, 2),
        "unit": unit,
        "vs_baseline": round(value_per_chip / baseline, 4),
        "n_chips": n_chips,
        "dtype": dtype,
        "platform": "tpu" if on_tpu else jax.devices()[0].platform,
    }
    # chip-fairness companion ratio: the resnet/gpt baselines are
    # A100-class measurements (312 TF/s bf16 peak); normalizing by each
    # chip's peak compares IMPLEMENTATION efficiency rather than silicon
    # size (v5e peak = 197 TF/s)
    if on_tpu and a100_baseline:
        from mxnet_tpu.flops import peak_flops_per_chip

        peak = peak_flops_per_chip()
        if peak:
            result["vs_baseline_per_peak_tflop"] = round(
                (value_per_chip / baseline) * (312e12 / peak), 4)
            result["baseline_chip_peak_tflops"] = 312.0
    result.update(extra_fields)
    result.update(_mfu_fields(net, {"data": (1,) + tuple(data_shape[1:])},
                              batch, n_iter, dt, n_chips,
                              trainer=trainer, placed=placed))
    print(json.dumps(result))


def _mfu_fields(net, unit_input_shapes, batch, n_iter, dt, n_chips,
                trainer=None, placed=None):
    """Model-FLOPs-utilization fields: analytic fwd FLOPs x3 for the
    train step (fwd + ~2x bwd) against the chip's bf16 peak.  When the
    compiled step is available, XLA's own cost model is recorded next to
    the analytic number so the MFU claim is cross-checkable."""
    from mxnet_tpu.flops import count_flops, peak_flops_per_chip

    fwd = count_flops(net, **unit_input_shapes)
    step_flops = 3 * fwd * batch
    achieved = step_flops * n_iter / dt
    peak = peak_flops_per_chip()
    fields = {"fwd_gflops_per_sample": round(fwd / 1e9, 3),
              "model_tflops_per_sec": round(achieved / 1e12, 2)}
    if peak:
        fields["mfu"] = round(achieved / (peak * n_chips), 4)
        fields["peak_tflops_per_chip"] = peak / 1e12
    # The .lower().compile() below takes the AOT path, which does NOT
    # reuse the jit cache — i.e. it recompiles the step.  That is cheap
    # on CPU (where the contract test uses it as the count_flops drift
    # gate) but minutes on TPU — so on TPU it is opt-in via
    # BENCH_XLA_COSTCHECK=1.
    import jax
    want_costcheck = os.environ.get(
        "BENCH_XLA_COSTCHECK",
        "0" if jax.default_backend() == "tpu" else "1") == "1"
    if trainer is not None and placed is not None and want_costcheck:
        import numpy as _np
        try:
            compiled = trainer._train_step.lower(
                trainer.params, trainer.opt_state, trainer.aux, placed,
                trainer._key, _np.float32(1.0)).compile()
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            xla_flops = float(ca.get("flops", 0.0))
        except Exception:
            # never crash a completed measurement over the cross-check;
            # the CPU contract test still fails loudly on drift because
            # the fields end up absent (test asserts their presence)
            xla_flops = 0.0
            ca = {}
        if xla_flops > 0:
            # cost_analysis reports the per-device SPMD program, so
            # compare against the per-chip analytic share
            fields["xla_step_gflops"] = round(xla_flops / 1e9, 2)
            fields["analytic_step_gflops"] = round(
                step_flops / n_chips / 1e9, 2)
            # bytes accessed -> arithmetic intensity (flops/byte): how
            # compute- vs HBM-bound XLA thinks the step is (the roofline
            # coordinate; v5e crossover is ~240 flops/byte at bf16 peak)
            xla_bytes = float(ca.get("bytes accessed", 0.0))
            if xla_bytes > 0:
                fields["xla_step_gbytes"] = round(xla_bytes / 1e9, 2)
                fields["arith_intensity_flops_per_byte"] = round(
                    xla_flops / xla_bytes, 1)
            try:
                ma = compiled.memory_analysis()
                peak = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                        + ma.output_size_in_bytes
                        - ma.alias_size_in_bytes)
                fields["xla_peak_hbm_gb"] = round(peak / 1e9, 3)
            except Exception:
                pass  # memory_analysis availability varies by backend
    return fields


def _timed_steps(jax, trainer, placed, n_warmup, n_iter):
    """Shared warmup + timed-loop harness over a ShardedTrainer step.

    Default mode dispatches one step per host call (back-to-back: each
    step's params depend on the previous, so the device serializes them
    and one final block covers the chain).  BENCH_DEVICE_LOOP=1 instead
    runs the whole timed loop ON DEVICE (fori_loop over the functional
    train step, trip count traced) and times the slope between two trip
    counts — no per-dispatch queue gap at all."""
    import numpy as np

    one = np.float32(1.0)

    if os.environ.get("BENCH_DEVICE_LOOP") == "1":
        def body(i, c):
            params, opt_state, aux, key = c
            params, opt_state, aux, _, key = trainer._train_step(
                params, opt_state, aux, placed, key, one)
            return (params, opt_state, aux, key)

        run_n = jax.jit(lambda n: jax.lax.fori_loop(
            0, n, body, (trainer.params, trainer.opt_state, trainer.aux,
                         trainer._key)))
        jax.block_until_ready(run_n(1))          # compile + warm
        n_lo, n_hi = 2, 2 + n_iter
        tic = time.perf_counter()
        jax.block_until_ready(run_n(n_lo))
        t_lo = time.perf_counter() - tic
        tic = time.perf_counter()
        jax.block_until_ready(run_n(n_hi))
        t_hi = time.perf_counter() - tic
        per_iter = max(t_hi - t_lo, 1e-9) / (n_hi - n_lo)
        return per_iter * n_iter      # callers divide by n_iter

    def step():
        trainer.params, trainer.opt_state, trainer.aux, outs, trainer._key = \
            trainer._train_step(trainer.params, trainer.opt_state,
                                trainer.aux, placed, trainer._key, one)
        return outs

    for _ in range(n_warmup):
        outs = step()
    jax.block_until_ready(outs)
    tic = time.perf_counter()
    for _ in range(n_iter):
        outs = step()
    jax.block_until_ready(outs)
    return time.perf_counter() - tic


def bench_cifar(jax, np, mx, on_tpu, n_chips):
    """Tertiary benchmark (BENCH_MODEL=cifar): the reference's FIRST
    headline table — CIFAR-10 inception-bn-28-small training img/sec
    (example/image-classification/README.md:218-224: 842 img/s on one
    GTX 980, 2943 img/s on the whole 4-GPU box at bs=128).  vs_baseline
    compares ONE chip against the full 4-GPU machine."""
    baseline_4gpu = 2943.0
    if on_tpu:
        batch_per_chip = int(os.environ.get("BENCH_BATCH", "512"))
        dtype = "bfloat16"
        layout = "NHWC"
        n_warmup, n_iter = 5, 20
    else:
        batch_per_chip = 8
        dtype = "float32"
        layout = "NCHW"
        n_warmup, n_iter = 2, 5
    batch = batch_per_chip * n_chips
    net = mx.models.inception_bn_small(num_classes=10, layout=layout)
    data_shape = ((batch, 28, 28, 3) if layout == "NHWC"
                  else (batch, 3, 28, 28))
    _train_throughput(
        jax, np, mx, net,
        input_shapes={"data": data_shape, "softmax_label": (batch,)},
        label_classes=10, dtype=dtype, n_warmup=n_warmup, n_iter=n_iter,
        on_tpu=on_tpu, n_chips=n_chips,
        metric="cifar_inception_bn_small_train_throughput",
        unit="images/sec/chip",
        per_chip_divisor=batch, baseline=baseline_4gpu,
        extra_fields={
            "baseline": "reference 4x GTX 980 whole-machine (2943 img/s); "
                        "single reference GPU = 842 img/s",
            "batch_per_chip": batch_per_chip, "layout": layout})


def bench_gpt(jax, np, mx, on_tpu, n_chips):
    """Secondary benchmark (BENCH_MODEL=gpt): transformer-LM training
    tokens/sec with the Pallas flash-attention op.  Baseline: an
    A100-class chip trains a ~25M-param GPT at roughly 400k tokens/s
    in public nanoGPT-style measurements."""
    baseline_tokens_per_sec = 400_000.0
    if on_tpu:
        batch_per_chip = int(os.environ.get("BENCH_BATCH", "16"))
        seq_len = 1024
        d_model, n_layers, n_heads, vocab = 512, 8, 8, 32768
        dtype = "bfloat16"
        n_warmup, n_iter = 3, 10
    else:
        batch_per_chip, seq_len = 4, 128
        d_model, n_layers, n_heads, vocab = 64, 2, 2, 256
        dtype = "float32"
        n_warmup, n_iter = 2, 4
    batch = batch_per_chip * n_chips

    fused_qkv = os.environ.get("BENCH_FUSED_QKV", "1") == "1"
    # sequence-major attention (no BSHD<->BHSD activation transposes —
    # the only activation transposes in the step HLO); sweepable, off
    # by default until on-chip numbers pick the winner
    attn_layout = os.environ.get("BENCH_ATTN_LAYOUT", "bhsd")
    # grouped-query attention (BENCH_KV_HEADS < n_heads shrinks the K/V
    # projections and, under bshd, the kernel's K/V streams)
    kv_heads = int(os.environ.get("BENCH_KV_HEADS", "0")) or None
    # fused CE head: skips the (B*S, vocab) probability materialization
    loss = os.environ.get("BENCH_GPT_LOSS", "softmax")
    # the llama-style recipe in one knob: rmsnorm + swiglu + rope + tied
    style = os.environ.get("BENCH_GPT_STYLE", "gpt2")
    style_kw = ({"norm": "rmsnorm", "mlp": "swiglu", "pos_embed": "rope",
                 "tie_embeddings": True} if style == "llama" else {})
    # multi-chip dp keeps the fused kernel too: ShardedTrainer sets the
    # ambient-mesh context and the FlashAttention op shard_maps its
    # Mosaic call over the batch axis (ops/attention.py spmd_attention)
    net = mx.models.gpt(vocab, seq_len, num_layers=n_layers,
                        d_model=d_model, num_heads=n_heads,
                        fused_qkv=fused_qkv, attn_layout=attn_layout,
                        kv_heads=kv_heads, loss=loss, **style_kw)
    _train_throughput(
        jax, np, mx, net,
        input_shapes={"data": (batch, seq_len),
                      "softmax_label": (batch, seq_len)},
        label_classes=vocab, dtype=dtype, n_warmup=n_warmup, n_iter=n_iter,
        on_tpu=on_tpu, n_chips=n_chips,
        metric="gpt_train_throughput", unit="tokens/sec/chip",
        per_chip_divisor=batch * seq_len, baseline=baseline_tokens_per_sec,
        extra_fields={"batch": batch, "seq_len": seq_len,
                      "d_model": d_model, "n_layers": n_layers,
                      "fused_qkv": fused_qkv, "attn_layout": attn_layout,
                      "kv_heads": kv_heads or n_heads, "loss": loss,
                      "style": style},
        a100_baseline=True,
        optimizer="adam", optimizer_params={"learning_rate": 3e-4},
        initializer=mx.initializer.Xavier(),
        # int32 ids: the bf16 compute dtype must not touch token inputs
        # (bf16 mantissa cannot represent ids > 256 exactly)
        input_dtypes={"data": np.int32, "softmax_label": np.int32})


if __name__ == "__main__":
    main()
