"""The latent routed experts' share of their roofline.  Need: for every
traced decode and prefill pass, the larger of the time its bytes take at
the HBM peak (the two matrices of the held experts that got a row, each
once, and every held pick's rows at the latent and the hidden width;
``arith_branch.moe_bytes``) and the time its operations take at the bf16
peak (``arith_branch.moe_flops``), from the pass's own router counts on
its span (``moe_experts_hit``, ``moe_picks_held``).

Over: the device time of the grouped products in the ``jit_decode``,
``jit_prefill`` and ``jit_chunk`` runs of the trace.  A trace's operation
names carry no ``jax.named_scope``, so the time is taken from what the
trace does name.  Where a program compiles the layer's short and full
paths (``ops.moe.short_path``: the prefill programs of 512 rows and
more), the gather, both grouped products and the weighted sum sit inside
ONE ``conditional`` that returns rows of the latent width and whose event
spans them all: that is the time.  Where a program has the one path
(decode, short prompts), nothing encloses the layer, and the time is
that of the grouped-product kernels (``gmm``, or an unnamed
``pallas_call``); the XLA gather and the 22-way weighted sum around them
are then not in it, and the share reads that much high.  Each program
takes the larger of the two."""

import re

import arith_branch
import span_readers

_PROGRAMS = ("jit_decode", "jit_prefill", "jit_chunk")
_SHAPE = re.compile(r"\[(?:\d+,)*(\d+)\]$")


def layer_seconds(ops, width):
    """Device seconds of the routed experts' work, from
    ``trace_reduce.reduce``'s ``ops``: per program the larger of its
    ``conditional``s that return rows of ``width`` and its grouped-product
    kernels."""
    enclosed, kernels = {}, {}
    for module, label, sec, kernel in ops:
        prog = next((p for p in _PROGRAMS if module.startswith(p)), None)
        if prog is None:
            continue
        if kernel and ("pallas_call" in label or "gmm" in label):
            kernels[prog] = kernels.get(prog, 0.0) + sec
        elif label.startswith("conditional"):
            m = _SHAPE.search(label)
            if m and int(m.group(1)) == width:
                enclosed[prog] = enclosed.get(prog, 0.0) + sec
    return sum(max(enclosed.get(p, 0.0), kernels.get(p, 0.0))
               for p in _PROGRAMS)


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("branch")
    span = ctx.get("trace_span")
    passes = span_readers.in_window(ctx, "serve.")
    if not tr or not peaks or not d or not span or not passes:
        return None
    dev_s = layer_seconds(tr["ops"], d["latent"])
    need_s = 0.0
    for s in passes:
        a = s[span_readers.ARGS]
        if s[span_readers.NAME] not in ("serve.decode", "serve.prefill") \
                or "moe_picks_held" not in a \
                or not span[0] <= s[span_readers.START] <= span[1]:
            continue
        need_s += max(
            arith_branch.moe_bytes(d, a["moe_experts_hit"],
                                   a["moe_picks_held"]) / peaks[1],
            arith_branch.moe_flops(d, a["moe_picks_held"]) / peaks[0])
    if dev_s <= 0 or need_s <= 0:
        return None
    return 100.0 * need_s / dev_s
