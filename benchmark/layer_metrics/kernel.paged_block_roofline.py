"""The paged-attention kernel's share of its roofline, which is memory
bandwidth, where a row's query is its block's B positions: the K and V
bytes the traced denoise passes had to read (every layer, each row's
committed context and its own block, the ``contexts`` of its
``serve.decode`` span; ``arith_diffusion.paged_bytes``) over the HBM
peak, divided by the summed device time of the ``paged_attention``
kernels inside ``jit_decode`` runs."""

import arith_diffusion
import span_readers


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("diffusion")
    span = ctx.get("trace_span")
    spans = span_readers.in_window(ctx, "serve.decode")
    if not tr or not peaks or not d or not span or not spans:
        return None
    contexts = [s[span_readers.ARGS]["contexts"]
                for s in span_readers.named(spans, "serve.decode")
                if "contexts" in s[span_readers.ARGS]
                and span[0] <= s[span_readers.START] <= span[1]]
    dev_s = sum(sec for module, label, sec, kernel in tr["ops"]
                if kernel and module.startswith("jit_decode")
                and "paged_attention" in label)
    if dev_s <= 0 or not contexts:
        return None
    need = sum(arith_diffusion.paged_bytes(d, c) for c in contexts)
    return 100.0 * (need / peaks[1]) / dev_s
