"""What the window's first runs of a program cost beyond any other run of
it: dispatch phase + device time of each ``first_run`` part of
``serve.pass`` less the median of the same (kind, bucket)'s other parts."""

import pass_readers


def read(ctx):
    return pass_readers.first_run_ms(ctx)
