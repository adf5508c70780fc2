"""Model FLOP/s utilisation of the whole window: the model's operations
for every position of every pass the window ran (a denoise or store pass
computes all B positions of each row's block, ``serve.decode``'s
``batch`` and ``contexts``; a prefill or chunk pass its span,
``serve.prefill``'s ``tokens`` from ``start``;
``arith_diffusion.denoise_flops`` / ``prefill_flops``) over the window's
length times the chip's bf16 peak.  The share of the whole step, beside
the kernels'.  A diffusion step computes B positions a row for fewer
committed tokens: about 5 passes of 4 positions commit 4 tokens, so this
reads the work done, not the tokens served."""

import arith_diffusion
import span_readers


def read(ctx):
    peaks, d, win = ctx.get("peaks"), ctx.get("diffusion"), ctx.get("window")
    spans = span_readers.in_window(ctx, "serve.")
    if not peaks or not d or not win or not spans:
        return None
    if win.get("window_s", 0) <= 0:
        return None
    ops = 0
    for s in spans:
        a = s[span_readers.ARGS]
        if s[span_readers.NAME] == "serve.decode" and "contexts" in a:
            ops += arith_diffusion.denoise_flops(d, a["batch"],
                                                 a["contexts"])
        elif s[span_readers.NAME] == "serve.prefill" and "start" in a:
            ops += arith_diffusion.prefill_flops(d, a["tokens"],
                                                 a["start"] + a["tokens"])
    if not ops:
        return None
    return 100.0 * ops / (win["window_s"] * peaks[0])
