"""Host-side span tracer emitting Chrome-trace-format JSON.

``profiler.py`` captures what the *device* does (XLA traces via
jax.profiler); this tracer captures what the *host* does around it —
data wait, dispatch, compile, optimizer update, serve step — as
complete ("ph": "X") events that Perfetto / chrome://tracing load
directly.  Open the host trace next to the XLA device trace and the
host phases line up against device time (docs/how_to/observability.md
shows the workflow).

Every span additionally enters a ``jax.profiler.TraceAnnotation`` when
one can be constructed, so if an XLA trace IS active
(``profiler.start()``), the same host phases appear *inside* the
device trace too — zero-cost when no capture is running.  The args a
span is created with ride the annotation as keywords (they land as
stats of the host event in the ``.xplane.pb``: ``serve.step`` carries
``step=<id>``), so device events and host spans can be joined by id.

**A span records what caused it.**  Every span gets an ``id`` (unique
per tracer) and the ``parent`` id of the span open on the same thread
when it started (a per-thread stack).  One clock: every timestamp is
``time.perf_counter`` seconds, the clock of ``StepProfiler``, of the
serve engine's request stamps and of the benchmark's driver.
:meth:`SpanTracer.spans` is the one public reader:
``(name, id, parent, start_s, end_s, args)`` tuples, so no caller
needs the Chrome-trace dicts or the tracer's epoch.

Events are buffered in a bounded in-memory RING (``max_events``): on
overflow the OLDEST event is evicted and counted in ``dropped``, so a
long-running serve always keeps the most recent tail — exactly the
window a post-mortem needs (dropping the newest would discard the
moments before the failure).  The buffer is written by
:meth:`SpanTracer.write` or the telemetry atexit dump.

Besides the implicit per-OS-thread tracks, callers may emit events on
*virtual* tracks (explicit ``tid`` + :meth:`SpanTracer.set_track_name`)
— the request tracer renders one track per in-flight serve request this
way, next to the host-thread spans.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time

__all__ = ["SpanTracer", "NOOP_SPAN"]


class _NoopSpan:
    """Reentrant do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


NOOP_SPAN = _NoopSpan()


class _Span:
    """One open span.  ``with tracer.span(...)`` stamps it from
    ``perf_counter``; :meth:`start` / :meth:`finish` take the instant
    from a caller that has read the clock already (``StepProfiler``
    closes one phase and opens the next on ONE read)."""

    __slots__ = ("_tracer", "name", "args", "id", "parent", "_t0", "_xla")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._xla = None

    def set(self, **args):
        """Add args learned while the span is open (a count, a source);
        they are recorded when it finishes."""
        self.args.update(args)

    def start(self, t=None):
        tracer = self._tracer
        stack = tracer._stack()
        self.id = next(tracer._ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        ann = tracer._annotation_cls()
        if ann is not None:
            try:
                self._xla = ann(self.name, **self.args)
                self._xla.__enter__()
            except Exception:
                self._xla = None
                tracer.xla_ann_errors += 1
        self._t0 = time.perf_counter() if t is None else t
        return self

    def finish(self, t=None, *exc):
        end = time.perf_counter() if t is None else t
        if self._xla is not None:
            try:
                self._xla.__exit__(*(exc or (None, None, None)))
            except Exception:
                # the host span must still land; the failure is
                # visible as a counter on the tracer (xla_ann_errors)
                self._tracer.xla_ann_errors += 1
        stack = self._tracer._stack()
        if self in stack:
            # pops anything an exception left open above this span too
            del stack[stack.index(self):]
        self._tracer._record(self.name, self._t0, end, self.args,
                             self.id, self.parent)

    __enter__ = start

    def __exit__(self, *exc):
        self.finish(None, *exc)
        return False


class SpanTracer:
    def __init__(self, max_events=200_000):
        self.max_events = int(max_events)
        self.dropped = 0
        # jax.profiler.TraceAnnotation enter/exit failures (counted,
        # never raised — spans still record host-side)
        self.xla_ann_errors = 0
        self._events = collections.deque()
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._track_names = {}         # explicit tid -> display name
        # perf_counter epoch all span timestamps are relative to
        self._t0 = time.perf_counter()
        self._ann_cls = False          # False = not resolved yet
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._local = threading.local()  # per-thread stack of open spans

    def _stack(self):
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def current(self):
        """Id of the innermost span open on this thread, or None."""
        stack = self._stack()
        return stack[-1].id if stack else None

    def _annotation_cls(self):
        if self._ann_cls is False:
            try:
                import jax

                self._ann_cls = jax.profiler.TraceAnnotation
            except Exception:
                self._ann_cls = None
        return self._ann_cls

    def span(self, name, **args):
        """Context manager recording one complete event around a block."""
        return _Span(self, name, args)

    def _push(self, ev):
        # ring semantics: evict the OLDEST event on overflow so the
        # buffer always holds the newest tail; evictions count in
        # ``dropped``
        with self._lock:
            while len(self._events) >= self.max_events:
                self._events.popleft()
                self.dropped += 1
            self._events.append(ev)

    def _record(self, name, start, end, args, sid, parent, tid=None,
                cat="host"):
        ev = {"name": name, "ph": "X", "cat": cat,
              "pid": self._pid,
              "tid": threading.get_ident() if tid is None else int(tid),
              "ts": (start - self._t0) * 1e6,
              "dur": max(0.0, (end - start) * 1e6),
              "span_id": sid, "parent_id": parent}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def add_complete(self, name, start, end, args=None, tid=None,
                     cat="host"):
        """Record a span after the fact (``start``/``end`` on
        ``perf_counter``).  On the caller's own thread its parent is the
        span open there now; on a virtual track (explicit ``tid``) it
        has none.  Returns the span's id."""
        sid = next(self._ids)
        self._record(name, start, end, args, sid,
                     self.current() if tid is None else None, tid, cat)
        return sid

    def instant(self, name, _tid=None, **args):
        """Zero-duration marker ("ph": "i")."""
        ev = {"name": name, "ph": "i", "s": "t", "cat": "host",
              "pid": self._pid,
              "tid": threading.get_ident() if _tid is None else int(_tid),
              "ts": (time.perf_counter() - self._t0) * 1e6}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def now(self):
        """Current timestamp on this tracer's clock (perf_counter —
        pass to :meth:`add_complete` start/end)."""
        return time.perf_counter()

    def spans(self, prefix=None, since=None, until=None):
        """The buffered spans as ``(name, id, parent, start_s, end_s,
        args)``, by start (a parent before the children that start with
        it), times in ``perf_counter`` seconds.  ``prefix`` keeps names
        that start with it (a string or a tuple of them); ``since`` /
        ``until`` keep spans that START in ``[since, until]``."""
        with self._lock:
            events = [e for e in self._events if e["ph"] == "X"]
        out = []
        for e in events:
            if prefix is not None and not e["name"].startswith(prefix):
                continue
            start = self._t0 + e["ts"] * 1e-6
            if (since is not None and start < since) \
                    or (until is not None and start > until):
                continue
            out.append((e["name"], e["span_id"], e["parent_id"], start,
                        start + e["dur"] * 1e-6, e.get("args") or {}))
        out.sort(key=lambda sp: (sp[3], sp[1]))   # a parent before its children
        return out

    def set_track_name(self, tid, name):
        """Name a virtual track (explicit-tid events, e.g. one per
        in-flight serve request)."""
        with self._lock:
            self._track_names[int(tid)] = str(name)

    def trace_events(self):
        """Buffered events plus the process/thread metadata records
        Perfetto uses for track names."""
        with self._lock:
            events = list(self._events)
            track_names = dict(self._track_names)
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "args": {"name": "mxtpu host"}}]
        for tid in sorted({e["tid"] for e in events}):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": track_names.get(
                             tid, f"host-thread-{tid}")}})
        return meta + events

    def write(self, path):
        """Write the Chrome-trace JSON object form (Perfetto /
        chrome://tracing / ``profiler.summarize``-style consumers)."""
        # event "ts" fields are relative to self._t0 (a perf_counter
        # stamp with no cross-process meaning); the anchor maps ts=0
        # to the wall clock so tools/timeline_report.py can align this
        # file with other replicas' traces and device captures
        # mxtpu-lint: disable=wall-clock (cross-process trace-stitch anchor)
        t0_epoch = time.time() - (time.perf_counter() - self._t0)
        payload = {"traceEvents": self.trace_events(),
                   "displayTimeUnit": "ms",
                   "otherData": {"producer": "mxnet_tpu.telemetry",
                                 "dropped_events": self.dropped,
                                 "t0_epoch": t0_epoch}}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return path

    def clear(self):
        with self._lock:
            self._events = collections.deque()
            self._track_names = {}
            self.dropped = 0
