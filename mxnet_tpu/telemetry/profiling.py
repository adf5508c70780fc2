"""Per-step host-overhead decomposition for the serve engine.

Answers "where did the *host* wall-clock go?" inside every
``Engine._step_inner`` iteration — the runtime complement to the lint
``host-sync`` checker's static map, and the measurement baseline for
ROADMAP 1(c)'s multi-step host loop (any future N-steps-per-turn
dispatch has to beat these numbers, phase by phase).

**One step instrument.**  ``begin(step_id)`` stamps the step start and
opens the first phase (``schedule``); every ``enter(phase)`` reads the
clock ONCE, closes the phase that was open at that instant and opens
``phase`` there; ``commit(...)`` closes the last phase and seals the
entry.  Every instant between begin and commit lies in exactly one
phase, so the per-step phase seconds sum to the step wall time by
construction (pinned in tests/test_profiling.py).  ``enter`` comes
BEFORE the work it names — a trace annotation cannot be named after
the fact.

**A step is one call of ``Engine.step()``, and the engine runs one pass
ahead of what it has read** (``serve/engine.py``, "The step loop"): a
call enqueues the NEXT pass's programs and only then blocks on the pass
the call before enqueued.  The phases say what the host did in the call,
whichever pass it did it for:

  schedule          admission fanout + scheduler.schedule() +
                    host-KV restore dispatch + utilization sampling,
                    for the pass this call enqueues
  prefill_dispatch  host operand build + async prefill/chunk dispatch
                    of the pass this call enqueues
  decode_dispatch   host operand build + async decode/draft/verify
                    dispatch (spec ingest rides here too), likewise
  device_wait       time blocked on the results of the pass this call
                    READS: the one enqueued by the call before, with
                    the next already queued behind it (the designed
                    ``_unpack_outs`` sync, plus the greedy-spec
                    drafted/verified syncs)
  host_sync         host bookkeeping of the pass just read: token
                    append, scheduler updates, request-trace events
  callbacks         step tail: flight record, stats/perf callbacks,
                    spec-window prune, telemetry gauges

**The same intervals as spans.**  With telemetry enabled every step is
also a ``SpanTracer`` span ``serve.step`` (args ``step``, and through
``note()`` the counts of the pass the step read), every phase interval a
span ``serve.<phase>``.  ``schedule``, the two dispatch phases and
``callbacks`` are children of ``serve.step``.  ``wait(name, ...)`` opens
the span of the pass being read, ``serve.prefill`` (one per prefill
pass) or ``serve.decode`` (one per step), with the counts the engine
kept from its dispatch, and the pass's ``device_wait`` and ``host_sync``
are its children: a pass's span is the host's wait for it and its
bookkeeping, NOT its dispatch, which happened a call earlier beside
another pass's wait.  (A speculative engine reads a pass inside the call
that enqueues it; its verify dispatch, between the draft's wait and its
own, stays inside the ``serve.decode`` that is open.)  All are stamped
from the SAME clock reads as the phase seconds (so the phase spans tile
their step exactly) and enter a ``jax.profiler.TraceAnnotation`` as they
open, so a device trace names an idle gap by the phase the host was in.
There is no second instrument: the engine makes one call per interval.

**The pass record: the device's time, from the same clock reads.**  With
telemetry on, every *part* of a pass (one prefill / chunk program, or the
decode program, each with the token-pool programs enqueued beside it) is
handed to :meth:`enqueued` in its dispatch phase and read in a
:meth:`wait`.  Its stamps are clock reads the phases take anyway:

  ``enq``    the read that closes the dispatch phase it was enqueued in
  ``ready``  the read that closes its ``device_wait`` (outputs on the host)
  ``exact``  the part was not done as that wait opened (``is_ready()``,
             no sync): the host really blocked, so ``ready`` is the
             instant the device finished it; and its start is known: the
             device was idle at ``enq``, or the read of the part before
             it blocked too

The device runs parts in the order they were enqueued, so a part runs
from ``max(enq, end of the part before)`` to its end, and the parts' device
times and the idle between them tile the device's timeline.  At each
phase turn the profiler polls ``is_ready()`` (no sync) on the newest
enqueued part while it is unread: when it is seen ready the device has
run dry inside the phase that just closed, which brackets the instant and
names the cause of the idle that follows (``no_work`` when the step
ended with nothing left to run, ``caller`` between two ``step()`` calls,
``device_wait`` when nothing was enqueued behind the pass being read).
Each part becomes one ``serve.pass`` span from device start to its end on
a virtual track (``serve-device``), args ``step`` (the call that
enqueued it: ``serve.step``'s id), ``kind``, ``bucket``, ``rows``,
``reason`` (its pass's, None = enqueued ahead), ``exact``, ``first_run``,
``dispatch_s``, ``wait_s``, ``idle_before_s`` / ``idle_max_s`` (the
bracket of the device's idle before it) and ``idle_cause``; counters
``mxtpu_serve_device_seconds_total{kind}``,
``mxtpu_serve_device_idle_seconds_total{cause}`` (the bracket's lower
end) and ``mxtpu_serve_first_runs_total{kind}``; and ``on_pass(kind,
bucket, seconds, exact)`` (the engine's ``PerfAttrib.timed``).  With
telemetry off none of it runs: no poll, no record, no clock read beyond
the phases'.

**Cost.**  An ``enter`` is one ``perf_counter`` read and a dict add —
the recorder is default ON (``MXTPU_STEP_PROFILE=0`` to disable, spans
included) and gated ≤1.02x tokens/s by the serve_bench ``step-profile``
A/B contract (PROFILE_BENCH.json).  With telemetry off it allocates no
span.  Disabled, the engine holds the NOOP recorder whose methods are
empty — zero clock reads on the hot path.

Surfaces: a bounded ring of per-step entries (``MXTPU_STEP_PROFILE_RING``,
default 256), cumulative per-phase totals, the ``step_profile`` engine
statusz section (which flight dumps embed via the statusz snapshot),
and ``mxtpu_step_phase_seconds{phase}`` histograms.  The statusz
section carries a perf_counter↔epoch clock anchor so
tools/timeline_report.py can place the rings on the fleet timeline.

Inertness contract (the PR 10/11 rule): the recorder never touches
tokens, program cache keys, or AOT fingerprints — on or off, greedy
output is byte-identical and ``_spec_digest`` unchanged.
"""

from __future__ import annotations

import collections
import time

from .. import telemetry as tel
from ..base import env_flag, env_int

__all__ = ["StepProfiler", "NOOP_STEP_PROFILER", "make_step_profiler",
           "PHASES", "ENV_ENABLE", "ENV_RING", "PHASE_SECONDS_BUCKETS"]

ENV_ENABLE = "MXTPU_STEP_PROFILE"        # step decomposition (default on)
ENV_RING = "MXTPU_STEP_PROFILE_RING"     # per-step entry ring size

PHASES = ("schedule", "prefill_dispatch", "decode_dispatch",
          "device_wait", "host_sync", "callbacks")

# host phases live well below program dispatches: 1us .. 100ms band
PHASE_SECONDS_BUCKETS = (1e-6, 2.5e-6, 5e-6, 1e-5, 2.5e-5, 5e-5,
                         1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
                         1e-2, 2.5e-2, 5e-2, 0.1, 0.25)

# device_wait and host_sync stay inside the span of the pass being read
# (``wait`` opens it); every other phase sits directly under serve.step
# and closes the pass that is open, but for a speculative engine's
# second dispatch of the decode it is reading
_PASS_PHASES = ("device_wait", "host_sync")

_STATUSZ_RECENT = 50     # ring tail carried on statusz / flight dumps


class _PassRecord:
    """One enqueued part's stamps (``StepProfiler.enqueued``); the
    device's run of it ends at ``end``, ``dry`` brackets that instant
    when the part was the last on the device's queue as it finished."""

    __slots__ = ("step", "kind", "bucket", "rows", "reason", "first_run",
                 "lead", "enq", "dispatch_s", "wait_s", "end", "dry",
                 "early")

    def __init__(self, step, kind, bucket, rows, reason, first_run, lead):
        self.step, self.kind, self.bucket, self.rows = step, kind, bucket, rows
        self.reason, self.first_run, self.lead = reason, first_run, lead
        self.enq = self.end = None
        self.dispatch_s = self.wait_s = 0.0
        self.dry = None           # (not done at, done at, phase between)
        self.early = False        # seen done as its wait opened: the
                                  # read did not block on it


class _NoopStepProfiler:
    """Shared disabled recorder: every hot-path call is a no-op pass.

    The engine holds this singleton when ``MXTPU_STEP_PROFILE=0`` so
    the step loop pays one attribute load + empty call per phase and
    zero clock reads."""

    enabled = False
    tracing = False

    def begin(self, step_id):
        pass

    def enter(self, phase, **args):
        pass

    def enqueued(self, kind, bucket, rows, lead, first_run):
        return None

    def wait(self, name, rec=None, **args):
        pass

    def note(self, **args):
        pass

    def commit(self, emitted=0, prefills=0, decodes=0, work_left=None):
        pass

    def recent(self, n=_STATUSZ_RECENT):
        return []

    def summary(self):
        return None

    def statusz(self):
        return {"enabled": False}


NOOP_STEP_PROFILER = _NoopStepProfiler()


class StepProfiler:
    """One per engine, constructed AFTER ``telemetry.enable()`` (the
    handle-caching asymmetry: the phase histogram handle is cached here
    at construction; spans follow ``telemetry.enabled()`` as each step
    begins).  Single-writer: only the engine step loop calls
    begin/enter/note/commit; readers (statusz handlers on HTTP threads) see a
    consistent tail because entries are appended whole."""

    enabled = True
    tracing = False               # this step is also recorded as spans
    reason = None                 # the engine's: why the pass it enqueues
                                  # was settled (None: enqueued ahead)

    def __init__(self, clock=time.perf_counter, ring=None, on_pass=None):
        self._clock = clock
        self.on_pass = on_pass    # (kind, bucket, seconds, exact) a part
        n = ring if ring is not None else env_int(ENV_RING, 256)
        self._ring = collections.deque(maxlen=max(1, int(n)))
        self._totals = {p: 0.0 for p in PHASES}
        self._steps = 0
        self._wall_s = 0.0
        self._emitted = 0
        self._cur = {}            # in-flight step: phase -> seconds
        self._step_id = 0
        self._t_begin = 0.0
        self._t_cursor = 0.0
        self._phase = "schedule"  # the phase open since the cursor
        self._spans = []          # open spans: step[, pass], phase
        # perf_counter<->epoch anchor: lets timeline_report place ring
        # entries (perf-domain t0s) on the fleet's wall-clock axis.
        # mxtpu-lint: disable=wall-clock (one-shot epoch anchor for trace stitching)
        self._anchor = {"perf": clock(), "epoch": time.time()}
        self._hist = tel.histogram(
            "mxtpu_step_phase_seconds",
            "host wall-time per serve-step phase", ("phase",),
            buckets=PHASE_SECONDS_BUCKETS)
        # the pass timer (telemetry on): parts enqueued in the open
        # phase, parts stamped and unread in device order, the part the
        # open device_wait reads, the last part read, its span track
        self._pending = []
        self._unread = collections.deque()
        self._reading = None
        self._last = None
        self._track = None
        self._c_device = tel.counter(
            "mxtpu_serve_device_seconds_total",
            "device seconds of serve passes from the step record's "
            "stamps, by program kind", ("kind",))
        self._c_idle = tel.counter(
            "mxtpu_serve_device_idle_seconds_total",
            "device idle seconds between serve passes (the lower end of "
            "each bracket), by what the host was doing as the device ran "
            "dry", ("cause",))
        self._c_first = tel.counter(
            "mxtpu_serve_first_runs_total",
            "parts whose program ran for the first time in this engine",
            ("kind",))

    # -- hot path (engine step loop only) --------------------------------
    def begin(self, step_id):
        """Stamp the step start and open its first phase, ``schedule``."""
        now = self._clock()
        if self._spans:
            self._close(0, now)   # the last step raised before commit:
            self._drop()          # its parts may never be read in order
        self._step_id = step_id
        since = self._t_cursor    # the last commit: the caller's turn since
        self._t_begin = self._t_cursor = now
        self._cur = {}
        self._phase = "schedule"
        self.tracing = tel.enabled()
        if self.tracing:
            self._poll(since, now, "caller")
            tr = tel.tracer()
            self._spans = [tr.span("serve.step", step=step_id).start(now),
                           tr.span("serve.schedule").start(now)]
        elif self._last is not None or self._unread or self._pending:
            self._drop()

    def _turn(self, phase):
        """Close the open phase's seconds and open ``phase``'s, at one
        clock read, which it returns."""
        now = self._clock()
        cur = self._cur
        cur[self._phase] = cur.get(self._phase, 0.0) + (now - self._t_cursor)
        if self.tracing:
            self._stamp(now)
        self._t_cursor = now
        self._phase = phase
        return now

    def enter(self, phase, **args):
        """Close the open phase and open ``phase`` at one clock read;
        ``args`` go to the phase's span."""
        now = self._turn(phase)
        if not self.tracing:
            return
        spans = self._spans
        inside = len(spans) == 3 and (
            phase in _PASS_PHASES or (phase == "decode_dispatch"
                                      and spans[1].name == "serve.decode"))
        self._close(2 if inside else 1, now)
        spans.append(tel.tracer().span("serve." + phase, **args).start(now))

    def wait(self, name, rec=None, **args):
        """Open the span of the pass the engine is about to read
        (``serve.prefill`` / ``serve.decode``, ``args`` the counts kept
        from its dispatch) and its ``device_wait``, at one clock read;
        ``rec`` is the part's record (:meth:`enqueued`), stamped ready
        when the wait closes.  ``name`` None: a second wait inside the
        pass span that is open (a greedy speculative engine's verify)."""
        now = self._turn("device_wait")
        if not self.tracing:
            return
        self._reading = rec
        if rec is not None:       # done already: the read will not block
            rec.early = rec.dry is not None or rec.lead.is_ready()
        spans, tr = self._spans, tel.tracer()
        if name is None:
            self._close(2, now)
        else:
            self._close(1, now)
            spans.append(tr.span(name, **args).start(now))
        spans.append(tr.span("serve.device_wait").start(now))

    def note(self, **args):
        """Counts for the span of the pass being read (``serve.prefill``
        / ``serve.decode``), or for ``serve.step`` outside one.  Call
        under ``if sprof.tracing`` where building the args costs."""
        if self.tracing:
            self._spans[-2].set(**args)

    def _close(self, keep, now):
        """Finish the open spans beyond the first ``keep``, innermost
        first, all at ``now``."""
        spans = self._spans
        while len(spans) > keep:
            spans.pop().finish(now)

    def commit(self, emitted=0, prefills=0, decodes=0, work_left=None):
        """Seal the in-flight step: the open phase ends here, the entry
        enters the ring, totals/histograms update.  ``work_left`` 0 (the
        engine has nothing to run): the device's idle from here on is
        ``no_work``."""
        now = self._turn(self._phase)
        cur = self._cur
        if self.tracing:
            self._close(0, now)
            last = self._last
            if (work_left == 0 and last is not None and last.dry
                    and not self._unread):
                last.dry = last.dry[:2] + ("no_work",)
        wall = now - self._t_begin
        entry = {
            "step": self._step_id,
            "t0": self._t_begin,
            "wall_s": wall,
            "emitted": int(emitted),
            "prefills": int(prefills),
            "decodes": int(decodes),
            "phases": cur,
        }
        self._ring.append(entry)
        self._cur = {}
        self._steps += 1
        self._wall_s += wall
        self._emitted += int(emitted)
        totals = self._totals
        hist = self._hist
        for phase, dt in cur.items():
            totals[phase] = totals.get(phase, 0.0) + dt
            hist.labels(phase=phase).observe(dt)

    # -- the pass timer (telemetry on) ----------------------------------
    def enqueued(self, kind, bucket, rows, lead, first_run):
        """A part's programs are on the device's queue (the engine calls
        this in the part's dispatch phase, with one of its outputs as
        ``lead``, having set ``reason`` for the pass).  Returns its
        record, for :meth:`wait`."""
        rec = _PassRecord(self._step_id, kind, int(bucket), int(rows),
                          self.reason, bool(first_run), lead)
        self._pending.append(rec)
        return rec

    def _stamp(self, now):
        """The pass timer at one phase turn: the phase open since the
        cursor closes at ``now``."""
        rec = self._reading
        if rec is not None and self._phase == "device_wait":
            self._reading = None
            self._done(rec, now)
        self._poll(self._t_cursor, now, self._phase)
        if self._pending:
            for rec in self._pending:
                rec.enq = now
                rec.dispatch_s = now - self._t_cursor
                self._unread.append(rec)
            self._pending.clear()

    def _poll(self, since, now, phase):
        """Whether the newest part on the device's queue finished between
        ``since`` and ``now``, while the host was in ``phase``."""
        if self._unread:
            rec = self._unread[-1]
            if rec.dry is None and rec.lead.is_ready():
                rec.dry = (since, now, phase)

    def _done(self, rec, now):
        """``rec``'s outputs reached the host at ``now``: its device time
        from the part before it, the idle between them, its span."""
        unread = self._unread
        if unread and unread[0] is rec:      # parts are read in order
            unread.popleft()
        rec.wait_s = now - self._t_cursor
        if rec.dry is None and not unread:
            # nothing behind it: the device ran dry inside this wait
            rec.dry = (self._t_cursor if rec.early else now, now,
                       "device_wait")
        rec.end = now if rec.dry is None else rec.dry[1]
        if rec.early:
            rec.end = min(rec.end, self._t_cursor)
        prev, self._last = self._last, rec
        start, known = rec.enq, True
        idle_lo = idle_hi = 0.0
        cause = None
        if prev is not None:
            if prev.dry is not None:
                lo, hi, cause = prev.dry
                idle_lo, idle_hi = max(0.0, start - hi), max(0.0, start - lo)
            # the part before ended exactly, or before this one was queued
            known = not prev.early or prev.end <= start
            start = max(start, prev.end)
        exact = not rec.early and known
        device_s = max(0.0, rec.end - start)
        self._c_device.labels(kind=rec.kind).inc(device_s)
        if idle_lo > 0.0:
            self._c_idle.labels(cause=cause).inc(idle_lo)
        if rec.first_run:
            self._c_first.labels(kind=rec.kind).inc()
        if self.on_pass is not None:
            self.on_pass(rec.kind, rec.bucket, device_s, exact)
        tr = tel.tracer()
        if self._track is None:
            from .request_trace import _acquire_track

            self._track = _acquire_track()
            tr.set_track_name(self._track, "serve-device")
        tr.add_complete("serve.pass", min(start, rec.end), rec.end, {
            "step": rec.step, "kind": rec.kind, "bucket": rec.bucket,
            "rows": rec.rows, "reason": rec.reason, "exact": exact,
            "first_run": rec.first_run, "dispatch_s": rec.dispatch_s,
            "wait_s": rec.wait_s, "idle_before_s": idle_lo,
            "idle_max_s": idle_hi, "idle_cause": cause}, tid=self._track)
        rec.lead = None           # the device buffer is the engine's again

    def _drop(self):
        """Telemetry went off: what the timer holds would go stale."""
        self._pending.clear()
        self._unread.clear()
        self._reading = self._last = None

    # -- surfaces --------------------------------------------------------
    def recent(self, n=_STATUSZ_RECENT):
        """The last ``n`` ring entries, oldest first."""
        if n <= 0:
            return []
        ring = list(self._ring)
        return ring[-n:]

    def fractions(self):
        """{phase: fraction of recorded wall time}, or None pre-step."""
        if self._wall_s <= 0.0:
            return None
        return {p: self._totals[p] / self._wall_s for p in PHASES}

    def summary(self):
        """Compact dict for monitor tails / fleet scrape rows."""
        return {
            "steps": self._steps,
            "wall_s": self._wall_s,
            "emitted": self._emitted,
            "fractions": self.fractions(),
        }

    def statusz(self):
        """The engine statusz ``step_profile`` section.  Unlike perf
        attribution this knob is default-on, so the section always
        reports its enabled state rather than collapsing to None."""
        # mxtpu-lint: disable=wall-clock (refreshed epoch anchor for trace stitching)
        anchor = {"perf": self._clock(), "epoch": time.time()}
        return {
            "enabled": True,
            "ring": self._ring.maxlen,
            "steps": self._steps,
            "wall_s": self._wall_s,
            "emitted": self._emitted,
            "totals_s": dict(self._totals),
            "fractions": self.fractions(),
            "clock_anchor": anchor,
            "recent": self.recent(),
        }


def make_step_profiler(clock=time.perf_counter, on_pass=None):
    """The engine's constructor hook: a live recorder when
    ``MXTPU_STEP_PROFILE`` is on (the default), the shared NOOP
    otherwise.  ``on_pass(kind, bucket, seconds, exact)`` hears every
    part the pass timer closes."""
    if env_flag(ENV_ENABLE, True):
        return StepProfiler(clock=clock, on_pass=on_pass)
    return NOOP_STEP_PROFILER
