"""90th percentile of (submit instant - due instant) over the measured
requests.  In a one-thread driver this is the wait for the step in flight
when the request fell due, not a starved generator."""

import arith


def read(ctx):
    return arith.percentile(ctx.get("late_ms") or [], 90)
