"""90th percentile, over every request due in the window, of (first token
visible - the instant the request was due); a request with no token by
the window's end enters as the time it has waited.  What a chat user
feels first, kept among the per-layer metrics because in this cell it
cannot carry a bound: a burst in the schedule sets it, and it answers a
5 % slower step with +63 % (my chip run, PR 24; PERF.md, Findings).  It is
the other side of the trade that ``tpot_ms_p90`` is on: admitting
prefills sooner lowers this and stretches the running requests' gaps."""


def read(ctx):
    return (ctx.get("end_to_end") or {}).get("ttft_ms_p90")
