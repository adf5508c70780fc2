"""Per-program performance attribution for the serve engine.

Answers "where did the device time go?" per compiled serve program —
(kind, bucket) = prefill/chunk/decode/draft/draft_chunk/verify/restore
x batch bucket — with two independently-gated halves:

**Cost table (default ON, ``MXTPU_PERF_ATTRIB=0`` to disable).**  At
program-resolve time (fresh trace, warm AOT artifact load, or a
process-local step-cache hit) the engine hands each compiled program
to :meth:`PerfAttrib.note_cost`, which records XLA's
``cost_analysis()`` — flops, bytes accessed, output bytes — keyed by
(kind, bucket).  Pure host-side bookkeeping at compile cadence: no
dispatch-path cost, no extra syncs.  When a backend reports no usable
cost analysis the engine's analytic fallback (``flops.gpt_token_flops``
/ ``gpt_prefill_flops``) fills the flops column instead.

**Device time of every pass (telemetry on).**  ``done(kind, bucket)``
counts each dispatch (a dict lookup and an integer add) and says
whether it was the program's first, which the step record's pass timer
carries as ``first_run``.  The timer (``telemetry/profiling.py``, "The
pass record") dates each part of a pass on the device from the clock
reads the step's phases take anyway — no sync is added, and the loop
keeps running ahead — and hands every part to :meth:`timed`: its device
seconds count toward the cost per 1k tokens; an EXACT part's also
enter a ``mxtpu_serve_program_seconds{kind,bucket}`` histogram and the
per-program table with derived achieved-TFLOP/s, MFU (vs
``flops.peak_flops_per_chip``), MBU (vs ``flops.peak_hbm_bytes_per_
chip``) and cost-per-1k-tokens gauges.  With telemetry off nothing is
timed and the timing columns stay empty.

Inertness contract (the PR 10/11 rule): attribution never touches
tokens, program cache keys, or AOT fingerprints — on or off, with
telemetry on or off, greedy output is byte-identical and
``_spec_digest`` unchanged (pinned in tests/test_perf_attrib.py).
"""

from __future__ import annotations

import math

from ..base import env_flag

__all__ = ["PerfAttrib", "ENV_ENABLE", "PROGRAM_SECONDS_BUCKETS"]

ENV_ENABLE = "MXTPU_PERF_ATTRIB"          # cost table (default on)

# finer-grained than metrics.DEFAULT_BUCKETS: bucketed serve programs
# live in the 10us .. 1s band on real chips
PROGRAM_SECONDS_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
                           1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
                           0.1, 0.25, 0.5, 1.0, 2.5)

_RECENT = 512    # per-program recent-time window for p99
_GAUGE_EVERY = 64  # timed steps between gauge refreshes (and every read)


class _Prog:
    """Per-(kind,bucket) dispatch/timing accumulator."""

    __slots__ = ("dispatches", "timed", "total_s", "recent")

    def __init__(self):
        self.dispatches = 0
        self.timed = 0
        self.total_s = 0.0
        self.recent = []          # bounded ring of exact device seconds

    def record(self, dt):
        if self.timed < _RECENT:
            self.recent.append(dt)
        else:
            self.recent[self.timed % _RECENT] = dt
        self.timed += 1
        self.total_s += dt

    def p99(self):
        if not self.recent:
            return None
        s = sorted(self.recent)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    def mean(self):
        return self.total_s / self.timed if self.timed else None


class PerfAttrib:
    """One per engine, constructed AFTER ``telemetry.enable()`` (the
    handle-caching asymmetry: metric handles are cached here at
    construction).  The engine is never referenced — like the program
    builders, this object must not retain a retired engine."""

    def __init__(self):
        self.enabled = env_flag(ENV_ENABLE, True)
        self._cost = {}           # (kind, bucket) -> cost-table entry
        self._prog = {}           # (kind, bucket) -> _Prog
        self._timed_steps = 0     # steps the pass timer ran in
        self._tokens = 0          # all emitted tokens (cheap int add)
        self._timed_tokens = 0    # emitted during timed steps
        self._device_s = 0.0      # device seconds of every timed part
        self.cost_errors = 0      # cost_analysis() refusals (statusz)
        try:
            from .. import flops as _flops

            self.peak_flops = _flops.peak_flops_per_chip()
            self.peak_bytes = _flops.peak_hbm_bytes_per_chip()
        except Exception:
            # off-accelerator / uninitialized backend: utilization
            # columns degrade to None, attribution still works
            self.peak_flops = None
            self.peak_bytes = None
            self.cost_errors += 1
        from .. import telemetry as tel

        self._hist = tel.histogram(
            "mxtpu_serve_program_seconds",
            "device seconds per serve program run, from the step "
            "record's exact pass stamps",
            ("kind", "bucket"), buckets=PROGRAM_SECONDS_BUCKETS)
        self._g_tflops = tel.gauge(
            "mxtpu_serve_achieved_tflops",
            "achieved TFLOP/s over timed program runs", ("kind",))
        self._g_mfu = tel.gauge(
            "mxtpu_serve_mfu",
            "achieved FLOP/s over peak_flops_per_chip", ("kind",))
        self._g_mbu = tel.gauge(
            "mxtpu_serve_mbu",
            "achieved bytes/s over peak HBM bandwidth", ("kind",))
        self._g_cost = tel.gauge(
            "mxtpu_serve_cost_per_1k_tokens_seconds",
            "timed device-seconds per 1000 emitted tokens")

    # -- cost table (compile cadence) -----------------------------------
    def note_cost(self, kind, bucket, fn, fallback_flops=None,
                  fallback_bytes=None):
        """Record ``fn``'s ``cost_analysis()`` under (kind, bucket);
        idempotent per key, tolerant of backends/fallback callables
        without one.  ``fallback_flops`` (the analytic estimate) fills
        the flops column when XLA reports none."""
        if not self.enabled:
            return
        key = (kind, int(bucket))
        if key in self._cost:
            return
        ent = {"flops": None, "bytes_accessed": None,
               "output_bytes": None, "source": None}
        try:
            ca = fn.cost_analysis()
            if isinstance(ca, (list, tuple)):   # older jax: list of dicts
                ca = ca[0] if ca else {}
            f = float(ca.get("flops", 0.0) or 0.0)
            if f > 0.0 and math.isfinite(f):
                ent["flops"] = f
                ent["source"] = "cost_analysis"
            b = float(ca.get("bytes accessed", 0.0) or 0.0)
            if b > 0.0 and math.isfinite(b):
                ent["bytes_accessed"] = b
            ob = float(ca.get("bytes accessed output", 0.0) or 0.0)
            if ob > 0.0 and math.isfinite(ob):
                ent["output_bytes"] = ob
        except Exception:
            # lazy-jit fallbacks have no .cost_analysis(); some
            # backends raise — the analytic column covers for them
            self.cost_errors += 1
        if ent["flops"] is None and fallback_flops:
            ent["flops"] = float(fallback_flops)
            ent["source"] = "analytic"
        if ent["bytes_accessed"] is None and fallback_bytes:
            ent["bytes_accessed"] = float(fallback_bytes)
        self._cost[key] = ent

    def cost(self, kind, bucket):
        """The cost-table entry for (kind, bucket), or None."""
        return self._cost.get((kind, int(bucket)))

    # -- device time (step cadence) -------------------------------------
    def done(self, kind, bucket):
        """Count one dispatch of (kind, bucket) — a dict lookup and an
        integer add — and say whether it was that program's first in
        this engine."""
        key = (kind, int(bucket))
        p = self._prog.get(key)
        if p is None:
            p = self._prog[key] = _Prog()
        p.dispatches += 1
        return p.dispatches == 1

    def timed(self, kind, bucket, seconds, exact):
        """One part of a pass as the step record's pass timer dated it:
        every part's device seconds count toward the cost per token, an
        exact part's also toward its program's row and histogram."""
        self._device_s += seconds
        if not exact:
            return
        key = (kind, int(bucket))
        p = self._prog.get(key)
        if p is None:
            p = self._prog[key] = _Prog()
        p.record(seconds)
        self._hist.labels(kind=kind, bucket=str(int(bucket))).observe(
            seconds)

    def on_step(self, emitted, timed=False):
        """Called once per engine step with the tokens emitted; ``timed``:
        the pass timer ran in it (token accounting + gauge refresh)."""
        self._tokens += int(emitted)
        if not (timed and self.enabled):
            return
        self._timed_steps += 1
        self._timed_tokens += int(emitted)
        if self._timed_steps % _GAUGE_EVERY == 0:
            self._update_gauges()

    # -- derived utilization --------------------------------------------
    def _kind_rates(self):
        """{kind: (seconds, achieved_flops, achieved_bytes)} over the
        exactly timed runs (flops/bytes from the cost table, so a
        missing entry contributes time but no utilization)."""
        agg = {}
        for (kind, bucket), p in self._prog.items():
            if not p.timed:
                continue
            ent = self._cost.get((kind, bucket)) or {}
            s, f, b = agg.get(kind, (0.0, 0.0, 0.0))
            s += p.total_s
            f += (ent.get("flops") or 0.0) * p.timed
            b += (ent.get("bytes_accessed") or 0.0) * p.timed
            agg[kind] = (s, f, b)
        return agg

    def _totals(self):
        """(seconds, flops, bytes) across all exactly timed runs."""
        s = f = b = 0.0
        for ks, kf, kb in self._kind_rates().values():
            s += ks
            f += kf
            b += kb
        return s, f, b

    def _update_gauges(self):
        for kind, (s, f, b) in self._kind_rates().items():
            if s <= 0.0:
                continue
            self._g_tflops.labels(kind=kind).set(f / s / 1e12)
            if self.peak_flops:
                self._g_mfu.labels(kind=kind).set(f / s / self.peak_flops)
            if self.peak_bytes:
                self._g_mbu.labels(kind=kind).set(b / s / self.peak_bytes)
        if self._timed_tokens:
            self._g_cost.set(1000.0 * self._device_s / self._timed_tokens)

    def mfu(self):
        """Overall MFU of the timed runs, or None (none / unknown
        peak)."""
        s, f, _ = self._totals()
        if s <= 0.0 or not self.peak_flops:
            return None
        return f / s / self.peak_flops

    def tok_flops(self):
        """Achieved FLOPs per emitted token over timed steps."""
        _, f, _ = self._totals()
        if not self._timed_tokens or f <= 0.0:
            return None
        return f / self._timed_tokens

    # -- surfaces --------------------------------------------------------
    def summary(self):
        """Compact dict for ServeMonitor tails and fleet scrape rows;
        None when attribution is disabled."""
        if not self.enabled:
            return None
        self._update_gauges()
        s, f, b = self._totals()
        out = {
            "timed": sum(p.timed for p in self._prog.values()),
            "achieved_tflops": (f / s / 1e12) if s > 0.0 else None,
            "mfu": self.mfu(),
            "mbu": (b / s / self.peak_bytes
                    if s > 0.0 and self.peak_bytes else None),
            "tok_flops": self.tok_flops(),
            "cost_per_1k_tokens_s": self._cost_per_1k(),
        }
        return out

    def _cost_per_1k(self):
        if not self._timed_tokens:
            return None
        return 1000.0 * self._device_s / self._timed_tokens

    def statusz(self):
        """The engine statusz ``perf`` section: overall goodput and the
        per-program table; None when disabled."""
        if not self.enabled:
            return None
        self._update_gauges()
        total_s, total_f, _ = self._totals()
        progs = []
        for key in sorted(set(self._cost) | set(self._prog)):
            kind, bucket = key
            ent = self._cost.get(key) or {}
            p = self._prog.get(key)
            mean = p.mean() if p else None
            flops = ent.get("flops")
            row = {
                "kind": kind,
                "bucket": bucket,
                "dispatches": p.dispatches if p else 0,
                "timed": p.timed if p else 0,
                "mean_s": mean,
                "p99_s": p.p99() if p else None,
                "flops": flops,
                "bytes_accessed": ent.get("bytes_accessed"),
                "output_bytes": ent.get("output_bytes"),
                "source": ent.get("source"),
                "achieved_tflops": (flops / mean / 1e12
                                    if flops and mean else None),
                "mfu": (flops / mean / self.peak_flops
                        if flops and mean and self.peak_flops else None),
                "share": (p.total_s / total_s
                          if p and total_s > 0.0 else None),
            }
            progs.append(row)
        out = {
            "enabled": True,
            "timed_steps": self._timed_steps,
            "timed_tokens": self._timed_tokens,
            "tokens": self._tokens,
            "device_seconds": self._device_s,
            "cost_errors": self.cost_errors,
            "peak_flops_per_chip": self.peak_flops,
            "peak_hbm_bytes_per_chip": self.peak_bytes,
            "achieved_tflops": (total_f / total_s / 1e12
                                if total_s > 0.0 else None),
            "mfu": self.mfu(),
            "tok_flops": self.tok_flops(),
            "cost_per_1k_tokens_s": self._cost_per_1k(),
            "programs": progs,
        }
        return out
