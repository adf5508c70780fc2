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

    def wait(self, name, **args):
        pass

    def note(self, **args):
        pass

    def commit(self, emitted=0, prefills=0, decodes=0):
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

    def __init__(self, clock=time.perf_counter, ring=None):
        self._clock = clock
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

    # -- hot path (engine step loop only) --------------------------------
    def begin(self, step_id):
        """Stamp the step start and open its first phase, ``schedule``."""
        now = self._clock()
        if self._spans:
            self._close(0, now)   # the last step raised before commit
        self._step_id = step_id
        self._t_begin = self._t_cursor = now
        self._cur = {}
        self._phase = "schedule"
        self.tracing = tel.enabled()
        if self.tracing:
            tr = tel.tracer()
            self._spans = [tr.span("serve.step", step=step_id).start(now),
                           tr.span("serve.schedule").start(now)]

    def _turn(self, phase):
        """Close the open phase's seconds and open ``phase``'s, at one
        clock read, which it returns."""
        now = self._clock()
        cur = self._cur
        cur[self._phase] = cur.get(self._phase, 0.0) + (now - self._t_cursor)
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

    def wait(self, name, **args):
        """Open the span of the pass the engine is about to read
        (``serve.prefill`` / ``serve.decode``, ``args`` the counts kept
        from its dispatch) and its ``device_wait``, at one clock read."""
        now = self._turn("device_wait")
        if not self.tracing:
            return
        spans, tr = self._spans, tel.tracer()
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

    def commit(self, emitted=0, prefills=0, decodes=0):
        """Seal the in-flight step: the open phase ends here, the entry
        enters the ring, totals/histograms update."""
        now = self._turn(self._phase)
        cur = self._cur
        if self.tracing:
            self._close(0, now)
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


def make_step_profiler(clock=time.perf_counter):
    """The engine's constructor hook: a live recorder when
    ``MXTPU_STEP_PROFILE`` is on (the default), the shared NOOP
    otherwise."""
    if env_flag(ENV_ENABLE, True):
        return StepProfiler(clock=clock)
    return NOOP_STEP_PROFILER
