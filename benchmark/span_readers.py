"""Reductions over the program's own spans: what the engine did in the
WHOLE window, by its own record, where ``readers.py`` reduces the
driver's stamps and the device trace of the window's last seconds.

A span is ``(name, id, parent, start_s, end_s, args)`` as
``mx.telemetry.tracer().spans()`` returns it, on ``time.perf_counter``,
the driver's clock.  The serve engine records (``mxnet_tpu/telemetry/
profiling.py``, ``serve/engine.py``, ``serve/scheduler.py``):

  serve.step            step, queue, running, blocks_in_use, emitted,
                        preemptions, work_left
    serve.schedule      (a phase: no args)
    serve.prefill       rid, kind, tokens, bucket, cached
      serve.prefill_dispatch, serve.device_wait, serve.host_sync
    serve.decode        batch, bucket
      serve.decode_dispatch, serve.device_wait, serve.host_sync
    serve.callbacks
  serve.request.queued  rid, resume      submit (or preemption) to admission
  serve.request.prefill rid, resume, passes, tokens   admission to first token
  serve.resolve         kind, bucket, source          one program made ready
    serve.resolve.build (source), serve.resolve.compile

Every function returns None when there is nothing to read: no window in
``ctx``, telemetry off (a ``--trace 0`` run), or a program without the
span reader (a commit before these spans).  ``mxnet_tpu`` is imported
only once ``ctx`` holds a window.
"""

import statistics

import arith

NAME, ID, PARENT, START, END, ARGS = range(6)


def dur_ms(span):
    return (span[END] - span[START]) * 1e3


def _tracer_spans(ctx):
    win = ctx.get("window")
    if not win or win.get("start") is None:
        return None, None
    import mxnet_tpu as mx

    spans = getattr(mx.telemetry.tracer(), "spans", None)
    if spans is None or not mx.telemetry.enabled():
        return None, None
    return win, spans


def in_window(ctx, prefix="serve."):
    """Spans named ``prefix``... that started inside the window."""
    win, spans = _tracer_spans(ctx)
    if win is None:
        return None
    return spans(prefix=prefix, since=win["start"], until=win["end"])


def before_window(ctx, prefix):
    """Spans named ``prefix``... that started before the window: set-up."""
    win, spans = _tracer_spans(ctx)
    if win is None:
        return None
    return spans(prefix=prefix, until=win["start"])


def named(spans, name):
    return [s for s in spans if s[NAME] == name]


# -- engine --------------------------------------------------------------------

def step_gaps_ms(spans):
    """End of one ``serve.step`` to the start of the next, over the steps
    whose predecessor ended with work left (an idle engine's wait for
    the next arrival is no gap)."""
    steps = named(spans, "serve.step")
    return [(b[START] - a[END]) * 1e3 for a, b in zip(steps, steps[1:])
            if a[ARGS].get("work_left")]


def gap_ms_p99(ctx):
    spans = in_window(ctx, "serve.step")
    return arith.percentile(step_gaps_ms(spans), 99) if spans else None


def span_ms_percentile(ctx, name, q):
    spans = in_window(ctx, name)
    if not spans:
        return None
    return arith.percentile([dur_ms(s) for s in named(spans, name)], q)


def dispatch_ms_per_step(spans):
    """Per step that dispatched anything: host time building operands
    and enqueueing programs (prefill, chunk and decode together); a
    dispatch phase belongs to the ``serve.step`` above it by parents."""
    by_id, total = {s[ID]: s for s in spans}, {}
    for s in spans:
        if s[NAME] not in ("serve.prefill_dispatch", "serve.decode_dispatch"):
            continue
        top = s
        while top is not None and top[NAME] != "serve.step":
            top = by_id.get(top[PARENT])
        if top is not None:
            total[top[ID]] = total.get(top[ID], 0.0) + dur_ms(s)
    return list(total.values())


def dispatch_ms_p50(ctx):
    spans = in_window(ctx)
    per_step = dispatch_ms_per_step(spans) if spans else None
    return statistics.median(per_step) if per_step else None


def resolve_s(ctx, name, source=None):
    """Summed seconds of the ``name`` spans before the window, all or
    those of one ``source`` (``trace``: a program traced afresh)."""
    spans = before_window(ctx, name)
    if not spans:
        return None
    return sum(s[END] - s[START] for s in named(spans, name)
               if source is None or s[ARGS].get("source") == source)


# -- scheduler -----------------------------------------------------------------

def prefill_step_share(ctx):
    """Of the steps that decoded, the share that also ran a prefill pass:
    how often a running request's gap held somebody else's prefill."""
    spans = in_window(ctx)
    if not spans:
        return None
    decoded = {s[PARENT] for s in named(spans, "serve.decode")}
    prefilled = {s[PARENT] for s in named(spans, "serve.prefill")}
    return 100.0 * len(decoded & prefilled) / len(decoded) if decoded \
        else None


def _first_by_rid(spans, name):
    out = {}
    for s in named(spans, name):
        if not s[ARGS].get("resume"):
            out.setdefault(s[ARGS]["rid"], s)
    return out


def request_waits_ms(spans, records, end):
    """(queue waits, prefill times) in ms over the submitted, unfailed
    requests of ``records`` (the requests due in the window).  One not
    admitted by ``end`` enters the first list as the wait it has so far
    and not the second; one admitted with no token yet enters the second
    as the time since its admission."""
    queued = _first_by_rid(spans, "serve.request.queued")
    prefill = _first_by_rid(spans, "serve.request.prefill")
    waits, prefills = [], []
    for rec in records:
        if rec.req is None or rec.failed:
            continue
        q = queued.get(rec.req.rid)
        if q is None:
            waits.append((end - (rec.due + rec.late)) * 1e3)
            continue
        waits.append(dur_ms(q))
        p = prefill.get(rec.req.rid)
        prefills.append(dur_ms(p) if p else (end - q[END]) * 1e3)
    return waits, prefills


def request_ms_p90(ctx, which):
    """``which``: 0 the queue wait, 1 admission to first token."""
    spans = in_window(ctx, "serve.request.")
    if spans is None:
        return None
    win = ctx["window"]
    return arith.percentile(
        request_waits_ms(spans, win.get("records") or [], win["end"])[which],
        90)


# -- program -------------------------------------------------------------------

def pad_share(ctx):
    """Share of the prefill and chunk programs' rows that were padding:
    1 - real positions / bucket, summed over the window's passes."""
    spans = in_window(ctx, "serve.prefill")
    passes = named(spans, "serve.prefill") if spans else []
    rows = sum(s[ARGS].get("bucket", 0) for s in passes)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(s[ARGS].get("tokens", 0) for s in passes) / rows)
