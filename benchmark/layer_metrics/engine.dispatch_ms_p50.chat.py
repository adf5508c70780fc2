"""Median host time per step spent building operands and enqueueing
programs (``serve.prefill_dispatch`` + ``serve.decode_dispatch``)."""

import span_readers


def read(ctx):
    return span_readers.dispatch_ms_p50(ctx)
