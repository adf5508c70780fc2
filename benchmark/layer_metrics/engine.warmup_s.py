"""Seconds the engine spent making programs ready before the window
(sum of ``serve.resolve``: trace or load, lower, compile or cache read)."""

import span_readers


def read(ctx):
    return span_readers.resolve_s(ctx, "serve.resolve")
