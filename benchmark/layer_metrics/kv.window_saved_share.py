"""Share of K/V blocks that the window layers' own block lifetimes save,
over the window's steps: 1 - (blocks held in both groups, each weighted by
its layers) / (what ONE table over all layers would hold for the same
requests: the global group's blocks in every layer).  From
``serve.step``'s ``blocks_global`` and ``blocks_window``."""

import span_readers


def read(ctx):
    d = ctx.get("moe")
    spans = span_readers.in_window(ctx, "serve.step")
    if not d or not spans:
        return None
    n_win = sum(d["window_layer"])
    n_glob = d["layers"] - n_win
    held = one = 0
    for s in span_readers.named(spans, "serve.step"):
        a = s[span_readers.ARGS]
        if "blocks_window" not in a:
            continue
        held += n_glob * a["blocks_global"] + n_win * a["blocks_window"]
        one += d["layers"] * a["blocks_global"]
    if one <= 0:
        return None
    return 100.0 * (1.0 - held / one)
