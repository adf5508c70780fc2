"""Share of the held experts that got at least one row, over the window's
decode passes: ``serve.decode``'s ``moe_experts_hit`` (summed over the
routed layers) over held experts x routed layers.  What a decode step
reads of the experts' bytes follows it."""

import arith_moe
import span_readers


def read(ctx):
    d = ctx.get("moe")
    spans = span_readers.in_window(ctx, "serve.decode")
    if not d or not spans:
        return None
    hit = [s[span_readers.ARGS]["moe_experts_hit"]
           for s in span_readers.named(spans, "serve.decode")
           if "moe_experts_hit" in s[span_readers.ARGS]]
    if not hit:
        return None
    return 100.0 * sum(hit) / (len(hit) * d["held"] * arith_moe.n_moe(d))
