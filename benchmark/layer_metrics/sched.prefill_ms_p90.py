"""90th percentile, over the admitted requests due in the window, of
admission to first token (``serve.request.prefill``: the request's own
passes plus the steps of others they waited through); one with no token
yet enters as the time since its admission."""

import span_readers


def read(ctx):
    return span_readers.request_ms_p90(ctx, 1)
