"""99th percentile of the host gap between one engine step's end and the
next one's start, over steps whose predecessor left work: a stall of the
host or the runtime shows here.  Whole window, the program's own spans."""

import span_readers


def read(ctx):
    return span_readers.gap_ms_p99(ctx)
