"""Mean decode batch per step over the window (engine counter)."""

import readers


def read(ctx):
    return readers.decode_batch_mean(ctx)
