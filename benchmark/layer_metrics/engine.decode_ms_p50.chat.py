"""Median wall time of a ``serve.decode`` pass (operand build, dispatch,
device wait, bookkeeping) over the WHOLE window, so at the batch the
window really ran, not the traced tail's."""

import span_readers


def read(ctx):
    return span_readers.span_ms_percentile(ctx, "serve.decode", 50)
