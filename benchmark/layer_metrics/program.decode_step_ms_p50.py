"""Median device duration of the decode program (``jit_decode`` events on
the trace's ``XLA Modules`` line)."""

import readers


def read(ctx):
    return readers.module_ms_p50(ctx, "jit_decode")
