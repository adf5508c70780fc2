#!/usr/bin/env python
"""Structural audit of the lowered bench train steps.

Lowers the EXACT ``ShardedTrainer._train_step`` each bench mode runs
(bench.py model configs, tiny trace shapes) to StableHLO — which is
platform-independent, so the audit needs no chip — and counts
layout-relevant ops.  The round-3 audits found: ResNet-50 NHWC/s2d = 3
transposes (all the FC-head weight),
CIFAR inception-bn-small = 3 (same), GPT bshd = zero activation
transposes.  ``tests/test_perf_contract.py`` pins these counts so a
layout regression (a new activation transpose slipping into the step)
fails CI on CPU alone.

``serve`` audits the SERVE program families the same way: the exact
bucketed programs ``serve.Engine`` dispatches (prefill/chunk/decode/
draft/draft_chunk/verify/restore, via ``engine._program_builder`` +
``_program_specs``), one JSON line per (kind, bucket) with op counts
plus ``cost_analysis()`` flops — the perf-attribution regression gate
(tests/test_perf_contract.py pins the counts on CPU).

Usage: python tools/hlo_audit.py [--tpu] [resnet|cifar|gpt|gpt_bshd|serve ...]
Prints one JSON line per model: {"model", "transposes", "convolutions",
"dot_generals", "all_to_alls"}.
"""

import contextlib
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@contextlib.contextmanager
def assume_tpu():
    """Make the op layer's TPU detection answer True, so a program
    lowered FOR a TPU from the CPU backend takes the Pallas paths (and
    compiled, not interpreted, kernels) it takes on hardware."""
    from mxnet_tpu.ops import pallas_util

    orig = pallas_util.on_tpu
    pallas_util.on_tpu = lambda: True
    try:
        yield
    finally:
        pallas_util.on_tpu = orig


def _lower_step(net, input_shapes, dtype="float32", input_dtypes=None,
                mesh=None, **trainer_kwargs):
    """Build the same dp ShardedTrainer bench.py builds; returns
    (trainer, placed) ready for ``lower_text``."""
    import numpy as np

    import mxnet_tpu as mx

    import jax

    # single-device mesh: the audit mirrors the real bench program (one
    # chip).  A multi-device mesh would also hit GSPMD's "Mosaic kernels
    # cannot be automatically partitioned" on the flash path — multi-chip
    # attention goes through ring/Ulysses shard_map or attn_impl="xla"
    # (models.gpt), not auto-partitioned Pallas.
    if mesh is None:
        mesh = mx.parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = mx.parallel.ShardedTrainer(
        net, input_shapes,
        mesh=mesh,
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2),
        dtype=dtype, input_dtypes=input_dtypes, **trainer_kwargs)
    rng = np.random.RandomState(0)
    data_shape = input_shapes["data"]
    if input_dtypes and np.issubdtype(input_dtypes.get("data"), np.integer):
        data = rng.randint(0, 32, data_shape)
    else:
        data = rng.uniform(-1, 1, data_shape).astype(np.float32)
    label_dtype = (input_dtypes.get("softmax_label", np.float32)
                   if input_dtypes else np.float32)
    label = rng.randint(0, 16, input_shapes["softmax_label"]).astype(
        label_dtype)
    placed = trainer._place_batch({"data": data, "softmax_label": label})
    return trainer, placed


def lower_text(trainer, placed, platform=None, force_flash=False):
    """StableHLO text of the train step.  ``platform="tpu"`` uses
    cross-platform AOT lowering (works without the chip — Mosaic
    lowers kernels at lowering time), which is how the audit checks
    the REAL TPU program from a CPU box.  ``force_flash`` makes the
    FlashAttention symbol op take the Pallas path the way it would on
    hardware (:func:`assume_tpu`)."""
    import numpy as np

    with assume_tpu() if force_flash else contextlib.nullcontext():
        traced = trainer._train_step.trace(
            trainer.params, trainer.opt_state, trainer.aux, placed,
            trainer._key, np.float32(1.0))
        if platform:
            lowered = traced.lower(lowering_platforms=(platform,))
        else:
            lowered = traced.lower()
    return lowered.as_text()


def audit_counts(text):
    """Count layout-relevant StableHLO ops in lowered text.

    ``activation_transposes`` counts transposes of rank >= 3 operands:
    rank-2 transposes are the mxnet (num_hidden, input) weight-storage
    convention meeting dot's layout (a few MB of weight traffic,
    negligible); rank >= 3 transposes shuffle activations (GB-scale at
    bench batch sizes) and are the thing a layout regression adds."""
    dims_lists = re.findall(r"stablehlo\.transpose[^\n]*dims = \[([^\]]*)\]",
                            text)
    act = sum(1 for d in dims_lists if len(d.split(",")) >= 3)
    return {
        "transposes": len(dims_lists),
        "activation_transposes": act,
        "convolutions": len(re.findall(r"stablehlo\.convolution", text)),
        "dot_generals": len(re.findall(r"stablehlo\.dot_general", text)),
        "all_to_alls": len(re.findall(r"all_to_all", text)),
    }


def _dims(tensor_type):
    """``tensor<2x64x8x4x8xbf16>`` -> ``(2, 64, 8, 4, 8)``."""
    return tuple(int(d) for d in re.findall(r"(\d+)x", tensor_type))


def cache_layer_slices(text, cache_shape):
    """The ``stablehlo.slice`` / ``dynamic_slice`` ops of lowered text
    whose RESULT is a whole layer of the stacked ``(L, num_blocks,
    block_size, Hkv, Dh)`` KV cache (any head count: a tp shard holds
    Hkv / tp).  Such a slice in front of a custom call or a gather is a
    copy of that layer's whole pool on the chip (PERF.md, PR 27), so
    the serve programs must lower with none."""
    _, nb, bs, _, dh = cache_shape
    found = []
    for line in text.splitlines():
        if not re.search(r"stablehlo\.(dynamic_)?slice\b", line):
            continue
        dims = _dims(line.rsplit("->", 1)[-1])
        while dims[:1] == (1,):
            dims = dims[1:]
        if len(dims) == 4 and (dims[0], dims[1], dims[3]) == (nb, bs, dh):
            found.append(line.strip()[:200])
    return found


def pool_sized_results(compiled_text, pool_shape):
    """``(name, op)`` of every instruction of COMPILED HLO text's entry
    computation whose result has as many elements as the cache pool
    ``pool_shape``, views and plumbing aside (parameters, bitcasts,
    tuples).  What a decode
    program that updates its pool in place leaves is the in-place writes
    (two scatter fusions an attention layer, K and V); a ``copy``, or a
    fusion that is not a write, is a second pool in HBM and a pass over
    the first (PERF.md, PR 27)."""
    want = math.prod(int(d) for d in pool_shape)
    return [(name, op) for name, _, n, op in entry_results(compiled_text)
            if n == want]


def entry_results(compiled_text):
    """``(name, dtype, elements, op)`` of every instruction of COMPILED
    HLO text's entry computation, views and plumbing aside (parameters,
    bitcasts, tuples): what the program writes to HBM."""
    found = []
    # the entry computation only: a fusion's body repeats its result
    entry = compiled_text[compiled_text.rindex("\nENTRY "):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
                     line)
        if not m or m.group(4) in ("parameter", "bitcast",
                                   "get-tuple-element", "tuple"):
            continue
        found.append((m.group(1), m.group(2),
                      math.prod(int(d) for d in m.group(3).split(",")),
                      m.group(4)))
    return found


def custom_call_operand_dims(text):
    """Per ``tpu_custom_call`` of lowered text, the dims of each of its
    operands (the type signature that ends the op's line)."""
    calls = []
    for line in text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        operands = line.rsplit(" : (", 1)[-1].rsplit(") ->", 1)[0]
        calls.append([_dims(t) for t in re.findall(r"tensor<[^>]*>",
                                                   operands)])
    return calls


# -- serve program families ---------------------------------------------------
# the serve-side analog of the train-step audit: lower the EXACT
# bucketed programs serve.Engine dispatches (engine._program_builder —
# the same builder traffic resolves through) and count layout ops +
# cost_analysis flops, so a lowering regression in the decode hot path
# fails CI on CPU alone (tests/test_perf_contract.py pins the counts)

# audited (kind, bucket) grid: one representative bucket per family
SERVE_KINDS = (("prefill", 8), ("chunk", 8), ("decode", 4),
               ("draft", 4), ("draft_chunk", 8), ("verify", 4),
               ("restore", 4))


def build_serve_engine(spec_k=2, dtype="float32", **kw):
    """A tiny CPU serve engine exposing every program family: target
    gpt + a smaller draft checkpoint (spec decoding on), host-tier
    geometry compatible with the restore program.  Program builders
    close over static config only, so lowering needs no warmup and no
    traffic.  ``dtype`` is the parameter (and so cache) dtype."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.base import np_dtype

    def tiny_params(net, seq):
        arg_shapes, _, _ = net.infer_shape(data=(1, seq),
                                           softmax_label=(1, seq))
        rng = np.random.RandomState(0)
        out = {}
        for name, shp in zip(net.list_arguments(), arg_shapes):
            if name in ("data", "softmax_label"):
                continue
            scale = 0.1 if name.endswith("weight") else 0.0
            out[name] = (rng.randn(*shp) * scale
                         + (1.0 if name.endswith("gamma") else 0.0)
                         ).astype(np.float32).astype(np_dtype(dtype))
        return out

    seq = 64
    net = mx.models.gpt(53, seq, num_layers=2, d_model=32, num_heads=4)
    draft = mx.models.gpt(53, seq, num_layers=1, d_model=16, num_heads=2)
    ekw = dict(block_size=4, num_blocks=64, max_batch=4,
               max_model_len=32, spec_k=spec_k,
               draft_params=tiny_params(draft, seq), draft_symbol=draft)
    ekw.update(kw)
    return mx.serve.Engine(tiny_params(net, seq), symbol=net, **ekw)


def serve_lower_text(eng, kind, bucket, platform=None):
    """StableHLO text of one serve program, traced from the engine's
    own builder + ShapeDtypeStruct signature (no live arrays, no
    compile) — ``platform="tpu"`` audits the real TPU lowering from a
    CPU-only CI box, exactly like the train-step path."""
    jitted = eng._program_builder(kind, bucket)
    specs = eng._program_specs(kind, bucket)
    traced = jitted.trace(*specs)
    if platform:
        lowered = traced.lower(lowering_platforms=(platform,))
    else:
        lowered = traced.lower()
    return lowered.as_text()


def serve_cost_flops(eng, kind, bucket):
    """cost_analysis() flops of the program compiled for the CURRENT
    backend (None when the backend reports none) — the number the
    engine's cost table captures at resolve time."""
    jitted = eng._program_builder(kind, bucket)
    specs = eng._program_specs(kind, bucket)
    try:
        ca = jitted.lower(*specs).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0) or 0.0)
        return f if f > 0.0 else None
    except Exception:
        return None


def build(model, batch=8):
    """Lower one bench model's train step (tiny trace shapes; same model
    constructors and layouts as bench.py's TPU configs)."""
    import numpy as np

    import mxnet_tpu as mx

    if model == "resnet":
        # bench.py TPU config: NHWC + space-to-depth stem (hw >= 64:
        # the s2d stem needs the full-size 7x7-equivalent entry, not
        # the cifar-style small-input stem)
        hw = 64
        net = mx.models.resnet(num_classes=1000, num_layers=50,
                               image_shape=(3, hw, hw), layout="NHWC",
                               stem="s2d")
        shapes = {"data": (batch, hw // 2, hw // 2, 12),
                  "softmax_label": (batch,)}
        return _lower_step(net, shapes)
    if model == "cifar":
        # bench_cifar: inception-bn-small NHWC
        net = mx.models.inception_bn_small(num_classes=10, layout="NHWC")
        shapes = {"data": (batch, 28, 28, 3), "softmax_label": (batch,)}
        return _lower_step(net, shapes)
    if model in ("gpt", "gpt_bshd"):
        # bench_gpt config family, tiny: the structural story is
        # per-layer, so 2 layers suffice
        seq = 32
        net = mx.models.gpt(211, seq, num_layers=2, d_model=64, num_heads=4,
                            fused_qkv=True,
                            attn_layout="bshd" if model == "gpt_bshd"
                            else "bhsd")
        shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}
        return _lower_step(net, shapes,
                           input_dtypes={"data": np.int32,
                                         "softmax_label": np.float32})
    raise SystemExit(f"unknown model {model!r}")


def main(argv):
    # a CPU-side audit: lowering needs no chip and must not take one
    os.environ["JAX_PLATFORMS"] = "cpu"
    tpu = "--tpu" in argv
    models = [a for a in argv if not a.startswith("--")] or [
        "resnet", "cifar", "gpt", "gpt_bshd"]
    for model in models:
        if model == "serve":
            # one line per serve program family: the bucketed programs
            # serve.Engine dispatches, traced from their real builders
            eng = build_serve_engine()
            try:
                for kind, bucket in SERVE_KINDS:
                    rec = {"model": f"serve_{kind}", "bucket": bucket,
                           "platform": "tpu" if tpu else "cpu"}
                    text = serve_lower_text(
                        eng, kind, bucket,
                        platform="tpu" if tpu else None)
                    rec.update(audit_counts(text))
                    rec["tpu_custom_calls"] = len(
                        re.findall(r"tpu_custom_call", text))
                    rec["cost_flops"] = serve_cost_flops(eng, kind,
                                                         bucket)
                    print(json.dumps(rec))
            finally:
                eng.shutdown()
            continue
        trainer, placed = build(model)
        rec = {"model": model, "platform": "tpu" if tpu else "cpu"}
        text = lower_text(trainer, placed,
                          platform="tpu" if tpu else None,
                          force_flash=tpu)
        rec.update(audit_counts(text))
        rec["tpu_custom_calls"] = len(re.findall(r"tpu_custom_call", text))
        print(json.dumps(rec))


if __name__ == "__main__":
    main(sys.argv[1:])
