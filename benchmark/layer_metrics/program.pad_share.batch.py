"""Share of the prefill and chunk programs' rows that were bucket
padding over the window: 1 - sum(tokens) / sum(bucket) of ``serve.prefill``."""

import span_readers


def read(ctx):
    return span_readers.pad_share(ctx)
