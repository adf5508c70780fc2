"""Memory-bandwidth utilisation of the denoise pass: all the bytes the
traced denoise passes needed (every weight that is not an expert's once a
pass, the experts hit, the K and V the rows attend to and the block's K
and V the pass writes; ``arith_diffusion.denoise_bytes`` from each
``serve.decode`` span's ``batch``, ``contexts`` and ``moe_experts_hit``)
over the HBM peak, divided by the summed device time of the
``jit_decode`` runs in the trace."""

import arith_diffusion
import span_readers
import trace_reduce


def read(ctx):
    tr, peaks, d = ctx.get("trace"), ctx.get("peaks"), ctx.get("diffusion")
    span = ctx.get("trace_span")
    spans = span_readers.in_window(ctx, "serve.decode")
    if not tr or not peaks or not d or not span or not spans:
        return None
    args = [s[span_readers.ARGS]
            for s in span_readers.named(spans, "serve.decode")
            if "contexts" in s[span_readers.ARGS]
            and "moe_experts_hit" in s[span_readers.ARGS]
            and span[0] <= s[span_readers.START] <= span[1]]
    dev_s = trace_reduce.module_time(tr, "jit_decode")
    if dev_s <= 0 or not args:
        return None
    need = sum(arith_diffusion.denoise_bytes(d, a["batch"], a["contexts"],
                                             a["moe_experts_hit"])
               for a in args)
    return 100.0 * (need / peaks[1]) / dev_s
