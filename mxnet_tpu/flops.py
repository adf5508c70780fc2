"""Analytic FLOP counting over symbol graphs.

Sums the multiply-accumulate-dominant operators (Convolution,
Deconvolution, FullyConnected, FlashAttention, batched matmul) from a
symbol's graph given concrete input shapes; elementwise/normalization
ops are ignored (sub-percent contributors on real models).  One MAC
counts as 2 FLOPs.

The reference has no FLOP tooling; this powers the MFU line in
``bench.py`` (model FLOPs / step-time / chip peak), the metric the
TPU performance story is judged by ("How to Scale Your Model" usage).

Usage::

    fwd = count_flops(net, data=(32, 3, 224, 224))
    train_step = 3 * fwd          # fwd + ~2x for backward
"""

from __future__ import annotations

import numpy as np

__all__ = ["count_flops", "peak_flops_per_chip", "peak_hbm_bytes_per_chip",
           "gpt_token_flops", "gpt_prefill_flops"]


def _prod(t):
    out = 1
    for v in t:
        out *= int(v)
    return out


def count_flops(symbol, **input_shapes) -> int:
    """Forward-pass FLOPs of ``symbol`` under the given input shapes.

    Counts Convolution / Deconvolution / FullyConnected / FlashAttention
    / dot-family nodes; everything else is treated as free.
    """
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape_partial(**input_shapes)
    heads = internals._heads
    shape_of = {}  # (node, idx) -> shape
    for (node, idx), shp in zip(heads, out_shapes):
        shape_of[(node, idx)] = shp

    total = 0
    for node, idx in heads:
        if idx != 0 or node.is_variable:
            continue
        op_name = node.op.name
        params = node.params
        out_shp = shape_of[(node, idx)]
        in_shp = (shape_of.get(node.inputs[0]) if node.inputs else None)
        if out_shp is None or in_shp is None:
            continue
        if op_name == "Convolution":
            kh, kw = params.kernel
            groups = getattr(params, "num_group", 1) or 1
            # output spatial positions x per-position dot of size
            # kh*kw*Cin/groups; layout-agnostic via element counts
            cin = (in_shp[-1] if getattr(params, "layout", "NCHW") == "NHWC"
                   else in_shp[1])
            total += 2 * _prod(out_shp) * kh * kw * cin // groups
        elif op_name == "Deconvolution":
            # transposed conv MACs scale with the INPUT extent: every
            # input position scatters a kh*kw*Cout patch
            kh, kw = params.kernel
            groups = getattr(params, "num_group", 1) or 1
            total += (2 * _prod(in_shp) * kh * kw
                      * params.num_filter // groups)
        elif op_name == "FullyConnected":
            in_dim = _prod(in_shp[1:])
            total += 2 * _prod(out_shp) * in_dim
        elif op_name == "FlashAttention":
            # (B, H, T, D): QK^T and PV are each 2*B*H*T^2*D
            b, h, t, d = in_shp
            total += 4 * b * h * t * t * d
        elif op_name in ("dot", "batch_dot", "linalg_gemm2"):
            rhs_shp = shape_of.get(node.inputs[1])
            if rhs_shp:
                # contraction length, transpose-flag agnostic:
                # |lhs|*|rhs| = (m k)(k n) and |out| = m n  =>  k^2
                k2 = (_prod(in_shp) * _prod(rhs_shp)) / max(_prod(out_shp), 1)
                total += int(2 * _prod(out_shp) * (k2 ** 0.5))
    return int(total)


def gpt_token_flops(n_layers, d_model, num_heads, head_dim, kv_heads,
                    vocab, context, d_ff=None, swiglu=False):
    """Analytic forward FLOPs for ONE token of a normalized ``gpt()``
    checkpoint attending over ``context`` cached positions (GQA-aware).

    Counts the matmul-dominant terms only — QKV/out projections, the
    per-head score and weighted-sum dots against the KV cache, the MLP
    (gate included under ``swiglu``), and the LM head — matching the
    :func:`count_flops` convention (1 MAC = 2 FLOPs, elementwise free).
    This is the per-token MFU denominator for serve-side attribution
    when a backend has no ``cost_analysis()``; the serve programs pad
    to bucket shapes, so pass the PADDED context (table capacity), not
    the live sequence length, to match compiled-program cost.
    """
    d_attn = num_heads * head_dim
    d_kv = kv_heads * head_dim
    d_ff = int(d_ff) if d_ff else 4 * d_model
    per_layer = 2 * d_model * d_attn          # Q projection
    per_layer += 2 * 2 * d_model * d_kv       # K + V projections (GQA)
    per_layer += 2 * d_attn * d_model         # output projection
    # scores (q . k) and weighted sum (p . v), 2 FLOPs/MAC each, over
    # the full padded context
    per_layer += 4 * num_heads * head_dim * int(context)
    mlp = 2 * d_model * d_ff + 2 * d_ff * d_model      # up + down
    if swiglu:
        mlp += 2 * d_model * d_ff                      # gate
    per_layer += mlp
    return int(n_layers) * per_layer + 2 * d_model * int(vocab)


def gpt_prefill_flops(n_layers, d_model, num_heads, head_dim, kv_heads,
                      vocab, seq_len, d_ff=None, swiglu=False,
                      logits_positions=None):
    """Analytic forward FLOPs for a dense ``seq_len``-token prefill of a
    normalized ``gpt()`` checkpoint.

    The serve prefill/chunk programs materialize the full (masked)
    TxT score matrix, so attention costs ``context = seq_len`` per
    position — not the triangle — which is what ``cost_analysis()``
    reports for the compiled program.  ``logits_positions`` bounds the
    LM-head term (1 for last-position-only programs; defaults to all
    positions).
    """
    T = int(seq_len)
    per_tok = gpt_token_flops(n_layers, d_model, num_heads, head_dim,
                              kv_heads, vocab, context=T, d_ff=d_ff,
                              swiglu=swiglu)
    head = 2 * d_model * int(vocab)
    total = T * (per_tok - head)
    n_logits = T if logits_positions is None else int(logits_positions)
    return total + n_logits * head


# Published per-chip peaks keyed by the exact ``device_kind`` jax reports:
# (bf16 FLOP/s, HBM bytes/s).  Source: Google Cloud TPU documentation,
# the "System architecture" page of each generation (v5e: 197 TFLOP/s
# bf16, 819 GB/s).  One table, exact keys: a TPU that is not listed is
# an error, never a neighbouring generation's peak.
_TPU_PEAKS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def _tpu_peaks(device):
    """The ``_TPU_PEAKS`` row for ``device`` (default: the first local
    device): None off-TPU, ValueError for a TPU the table does not list."""
    import jax

    d = device or jax.devices()[0]
    if d.platform != "tpu":
        return None
    try:
        return _TPU_PEAKS[d.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for TPU device_kind "
            f"{d.device_kind!r}; add its row to flops._TPU_PEAKS with "
            "its source") from None


def peak_flops_per_chip(device=None):
    """Peak bf16 FLOP/s of the local accelerator: the MFU denominator.
    None off-TPU; raises for a TPU kind the table does not list."""
    row = _tpu_peaks(device)
    return None if row is None else row[0]


def peak_hbm_bytes_per_chip(device=None):
    """Peak HBM bandwidth (bytes/s) of the local accelerator: the MBU
    denominator, the figure bandwidth-bound decode is judged against.
    None off-TPU; raises for a TPU kind the table does not list."""
    row = _tpu_peaks(device)
    return None if row is None else row[1]
