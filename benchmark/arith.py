"""The benchmark's own arithmetic: chip peaks, operations and bytes.

Copied from ``mxnet_tpu/flops.py`` as it stood at PR 21 (``_TPU_PEAKS``,
``count_flops``, ``gpt_token_flops``, ``gpt_prefill_flops``) so that a
later change to the program cannot move the yardstick;
``benchmark/tests/test_benchmark.py`` pins each copy to its original at
one shape.  One multiply-accumulate counts as 2 operations; elementwise
and normalisation work counts as free.
"""

# (bf16 FLOP/s, HBM bytes/s) per chip, keyed by the exact ``device_kind``
# jax reports.  Source: Google Cloud TPU documentation, "TPU v5e" system
# architecture page: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.  A kind
# that is not listed is an error, never a neighbour's peak.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}


def peaks(device_kind):
    """The ``PEAKS`` row for ``device_kind``; KeyError-free callers get a
    ValueError that says what to do."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add its "
            "row to benchmark/arith.py PEAKS with its source") from None


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; ``inf`` entries (failed requests) sort last."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == float("inf"):
        return vals[hi] if pos > lo else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def _prod(t):
    out = 1
    for v in t:
        out *= int(v)
    return out


def count_flops(symbol, **input_shapes):
    """Forward operations of a symbol graph under the given input shapes
    (copy of ``mxnet_tpu.flops.count_flops``): Convolution,
    Deconvolution, FullyConnected, FlashAttention and dot-family nodes."""
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape_partial(**input_shapes)
    heads = internals._heads
    shape_of = dict(zip(heads, out_shapes))
    total = 0
    for node, idx in heads:
        if idx != 0 or node.is_variable:
            continue
        op, params = node.op.name, node.params
        out_shp = shape_of[(node, idx)]
        in_shp = shape_of.get(node.inputs[0]) if node.inputs else None
        if out_shp is None or in_shp is None:
            continue
        if op == "Convolution":
            kh, kw = params.kernel
            groups = getattr(params, "num_group", 1) or 1
            cin = (in_shp[-1] if getattr(params, "layout", "NCHW") == "NHWC"
                   else in_shp[1])
            total += 2 * _prod(out_shp) * kh * kw * cin // groups
        elif op == "Deconvolution":
            kh, kw = params.kernel
            groups = getattr(params, "num_group", 1) or 1
            total += 2 * _prod(in_shp) * kh * kw * params.num_filter // groups
        elif op == "FullyConnected":
            total += 2 * _prod(out_shp) * _prod(in_shp[1:])
        elif op == "FlashAttention":
            b, h, t, d = in_shp
            total += 4 * b * h * t * t * d
        elif op in ("dot", "batch_dot", "linalg_gemm2"):
            rhs = shape_of.get(node.inputs[1])
            if rhs:
                k2 = (_prod(in_shp) * _prod(rhs)) / max(_prod(out_shp), 1)
                total += int(2 * _prod(out_shp) * (k2 ** 0.5))
    return int(total)


def gpt_token_flops(n_layers, d_model, num_heads, head_dim, kv_heads, vocab,
                    context, d_ff, swiglu):
    """Forward operations for ONE token of a gpt() decoder attending over
    ``context`` positions (copy of ``mxnet_tpu.flops.gpt_token_flops``)."""
    d_attn = num_heads * head_dim
    d_kv = kv_heads * head_dim
    per_layer = 2 * d_model * d_attn              # Q
    per_layer += 2 * 2 * d_model * d_kv           # K, V
    per_layer += 2 * d_attn * d_model             # output projection
    per_layer += 4 * num_heads * head_dim * int(context)   # q.k and p.v
    per_layer += (6 if swiglu else 4) * d_model * d_ff     # MLP
    return int(n_layers) * per_layer + 2 * d_model * int(vocab)


def gpt_prefill_flops(n_layers, d_model, num_heads, head_dim, kv_heads, vocab,
                      seq_len, d_ff, swiglu, logits_positions=None,
                      context=None):
    """Forward operations of a ``seq_len``-token prefill pass (copy of
    ``mxnet_tpu.flops.gpt_prefill_flops``).  Each position attends over
    ``context`` positions (default ``seq_len``: the serve programs build
    the whole masked score matrix, not the triangle); the head runs on
    ``logits_positions`` positions (default all)."""
    T = int(seq_len)
    ctx = T if context is None else int(context)
    per_tok = gpt_token_flops(n_layers, d_model, num_heads, head_dim,
                              kv_heads, vocab, ctx, d_ff, swiglu)
    head = 2 * d_model * int(vocab)
    n_logits = T if logits_positions is None else int(logits_positions)
    return T * (per_tok - head) + n_logits * head


def model_prefill_flops(model, passes):
    """Operations the algorithm needs for a list of prefill passes, each
    ``(span, end)``: ``span`` prompt tokens computed, attending over the
    ``end`` positions cached by the end of the pass, one head position
    each.  Real tokens only: bucket padding is the program's waste, not
    the algorithm's need, so it lowers the MFU it is divided into."""
    total = 0
    for span, end in passes:
        total += gpt_prefill_flops(
            model["num_layers"], model["d_model"], model["num_heads"],
            model["d_model"] // model["num_heads"], model["kv_heads"],
            model["vocab"], span, model["d_ff"], True, logits_positions=1,
            context=end)
    return total


def paged_attention_bytes(model, contexts, itemsize=2):
    """Bytes of K and V one decode step's attention has to read: for each
    running request its ``context`` cached positions x 2 (K and V) x
    kv_heads x head_dim x itemsize, in every layer.  Queries and outputs
    (a few KB) are left out; the kernel is bandwidth-bound on the cache."""
    head_dim = model["d_model"] // model["num_heads"]
    per_pos = 2 * model["kv_heads"] * head_dim * itemsize
    return int(sum(contexts)) * per_pos * model["num_layers"]
