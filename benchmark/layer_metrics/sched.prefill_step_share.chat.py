"""Of the window's steps that decoded, the share that also carried a
prefill or chunk pass (each stretches every running request's gap)."""

import span_readers


def read(ctx):
    return span_readers.prefill_step_share(ctx)
