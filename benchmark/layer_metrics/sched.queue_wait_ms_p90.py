"""90th percentile, over the requests due in the window, of submit to
admission (``serve.request.queued``, first admission); one not admitted
by the window's end enters as the wait it has so far.  With
``gen.late_ms_p90`` and ``sched.prefill_ms_p90`` it splits
``sched.ttft_ms_p90``."""

import span_readers


def read(ctx):
    return span_readers.request_ms_p90(ctx, 0)
