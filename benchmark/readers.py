"""Reductions the per-layer readers share.  A reader is one file under
``layer_metrics/`` named after its metric, with one ``read(ctx)`` that
returns a number, or None when there is nothing to read (the metric is
then left out of the line).  ``ctx`` is the run's context: the cell's
own records (driver stamps, engine counters) plus, from ``run.py``,
``trace`` (``trace_reduce.reduce``'s summary or None), ``trace_span``
(host clock at the trace's start and stop), ``peaks`` ((FLOP/s, bytes/s)
or None off the chip), ``config``, ``traffic`` and ``end_to_end``.

Serve steps are tuples (end stamp, requests decoded, sum of their
contexts, prefill passes [(span, end)], block utilisation, the engine's
own decode batch, the waiting queue's depth)."""

import statistics

import trace_reduce


def traced_steps(ctx):
    """The window's steps that ran inside the traced span (the trace
    starts and stops between steps, so these are whole steps)."""
    span = ctx.get("trace_span")
    if not span or not ctx.get("steps"):
        return []
    return [s for s in ctx["steps"] if span[0] < s[0] <= span[1]]


def decode_batch_mean(ctx):
    """Mean decode batch over the window's steps that decoded, from the
    engine's ``StatsRecorder.on_step`` record of each step."""
    sizes = [s[5] for s in ctx.get("steps", ()) if s[5]]
    return sum(sizes) / len(sizes) if sizes else None


def host_share(ctx):
    """1 - device_wait / all phases, from ``StepProfiler`` totals over the
    window (its phases sum to the step's wall time by construction)."""
    ph = ctx.get("phase_seconds")
    if not ph or sum(ph.values()) <= 0:
        return None
    return 100.0 * (1.0 - ph.get("device_wait", 0.0) / sum(ph.values()))


def module_ms_p50(ctx, *prefixes):
    tr = ctx.get("trace")
    if not tr:
        return None
    durs = [d for k, v in tr["modules"].items() if k.startswith(prefixes)
            for d in v]
    return statistics.median(durs) * 1e3 if durs else None


def prefill_share(ctx):
    """Device time of the prefill and chunk programs over device busy
    time, in the traced span."""
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * trace_reduce.module_time(
        tr, "jit_prefill", "jit_chunk") / tr["busy_s"]
