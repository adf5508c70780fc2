"""Decode-pass rows a committed block took, over the window: the rows of
the window's ``serve.decode`` passes (``batch``) over the blocks their
store passes committed (``committed``).  A block of 4 at 4 steps of one
token takes 5, four denoise passes and the store pass; a request's first
block fewer where the prompt's tail fills some of its positions."""

import span_readers


def read(ctx):
    spans = span_readers.in_window(ctx, "serve.decode")
    if not spans:
        return None
    args = [s[span_readers.ARGS]
            for s in span_readers.named(spans, "serve.decode")
            if "committed" in s[span_readers.ARGS]]
    committed = sum(a["committed"] for a in args)
    if not committed:
        return None
    return sum(a["batch"] for a in args) / committed
