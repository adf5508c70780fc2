"""Median device time of the window's most-run decode program, over its
parts whose stamps are exact (``serve.pass``, the step record's pass
timer), over the whole window: ``program.decode_step_ms_p50`` from the
program's own clock reads, and unmoved by the batch's mix of buckets."""

import pass_readers


def read(ctx):
    return pass_readers.decode_pass_ms_p50(ctx)
