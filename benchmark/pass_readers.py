"""Reductions over the step record's pass timer: the ``serve.pass`` spans
(``mxnet_tpu/telemetry/profiling.py``, "The pass record"), one per part of
a pass (a prefill or chunk program, or the decode program, with the token
pool's small programs beside it), from the instant the device started it
to the instant it finished, over the WHOLE window.  ``readers.py`` reads
the same programs from the device trace of the window's last seconds.

A span's args: ``step`` (the ``serve.step`` that enqueued it), ``kind``,
``bucket``, ``rows``, ``reason`` (None: enqueued behind an unread pass),
``exact`` (the host blocked on it, so its end is the device's), ``first_run``
(the program's first run in the engine), ``dispatch_s`` (the host phase that
enqueued it), ``wait_s``, ``idle_before_s`` / ``idle_max_s`` (the bracket of
the device's idle before it) and ``idle_cause`` (the host phase in which
the device ran dry, ``caller`` between two steps, ``no_work`` when the
engine had nothing to run).

Every function returns None when there is nothing to read: a ``--trace 0``
run, or a program without the pass timer (a commit before it).
"""

import statistics

import span_readers

NAME, START, END, ARGS = (span_readers.NAME, span_readers.START,
                          span_readers.END, span_readers.ARGS)


def passes(ctx):
    """The window's ``serve.pass`` spans: those the device started AND
    ended in it.  The pass still in flight as the window closes is read
    after it, once the cell looks again (seconds later, after its own
    checks), and a part's end is then only that look."""
    spans = span_readers.in_window(ctx, "serve.pass")
    if not spans:
        return None
    end = ctx["window"]["end"]
    return [s for s in span_readers.named(spans, "serve.pass")
            if s[END] <= end]


def device_s(span):
    return span[END] - span[START]


def decode_ms_by_bucket(ctx):
    """{bucket: device milliseconds of each of the window's decode parts
    with exact stamps}, or None."""
    by = {}
    for p in passes(ctx) or ():
        if p[ARGS]["kind"] == "decode" and p[ARGS]["exact"]:
            by.setdefault(p[ARGS]["bucket"], []).append(device_s(p) * 1e3)
    return by or None


def decode_pass_ms_p50(ctx):
    """Median device time of the decode program the window ran most
    (exact parts; the larger bucket on a tie): a batch that moves between
    two buckets moves no program's time, so one median over both would
    move with the mix."""
    by = decode_ms_by_bucket(ctx)
    if by is None:
        return None
    _, bucket = max((len(ms), b) for b, ms in by.items())
    return statistics.median(by[bucket])


def prefill_pass_share(ctx):
    """Device seconds of the prefill and chunk parts over all parts'."""
    ps = passes(ctx)
    total = sum(device_s(p) for p in ps or ())
    if total <= 0:
        return None
    return 100.0 * sum(device_s(p) for p in ps
                       if p[ARGS]["kind"] in ("prefill", "chunk")) / total


def starved_share(ctx):
    """Device idle between parts while the engine had work (every cause
    but ``no_work``; the lower end of each bracket) over ``window_s``."""
    ps = passes(ctx)
    if ps is None:
        return None
    idle = sum(p[ARGS]["idle_before_s"] for p in ps
               if p[ARGS]["idle_cause"] not in (None, "no_work"))
    return 100.0 * idle / ctx["window"]["window_s"]


def first_run_ms(ctx):
    """What the window's first runs cost beyond a run of the same program:
    over parts with ``first_run``, the sum of (dispatch phase + device
    time) less the median of the same (kind, bucket)'s other parts in the
    window.  A first run whose program ran no other time there is left
    out: nothing says what a run of it costs."""
    ps = passes(ctx)
    if ps is None:
        return None
    cost = {}
    for p in ps:
        key = (p[ARGS]["kind"], p[ARGS]["bucket"])
        cost.setdefault(key, ([], []))[bool(p[ARGS]["first_run"])].append(
            p[ARGS]["dispatch_s"] + device_s(p))
    extra = sum(sum(firsts) - len(firsts) * statistics.median(others)
                for others, firsts in cost.values() if firsts and others)
    return extra * 1e3
