"""How unevenly the router loads the held experts, over the window's
decode passes: the busiest held expert's rows (``moe_load_max``, summed
over the routed layers) over the mean rows of a held expert (held picks /
held experts, summed over the same layers).  1 is even."""

import span_readers


def read(ctx):
    d = ctx.get("moe")
    spans = span_readers.in_window(ctx, "serve.decode")
    if not d or not spans:
        return None
    args = [s[span_readers.ARGS]
            for s in span_readers.named(spans, "serve.decode")
            if "moe_load_max" in s[span_readers.ARGS]]
    mean = sum(a["moe_picks_held"] for a in args) / d["held"]
    if not args or mean <= 0:
        return None
    return sum(a["moe_load_max"] for a in args) / mean
