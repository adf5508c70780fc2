"""Median device duration of the train-step program (trace)."""

import readers


def read(ctx):
    return readers.module_ms_p50(ctx, "jit_train_step")
