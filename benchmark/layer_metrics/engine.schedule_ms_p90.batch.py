"""90th percentile of the ``serve.schedule`` phase: admission, block
allocation and prefix hashing, which a closed loop pays at every refill."""

import span_readers


def read(ctx):
    return span_readers.span_ms_percentile(ctx, "serve.schedule", 90)
