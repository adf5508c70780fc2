"""Persistent XLA compile-cache wiring.

JAX ships a persistent compilation cache (compiled executables keyed by
HLO module + compile options + jax version + backend fingerprint,
written as ``<name>-<key>-cache`` files).  It is off by default and its
1-second minimum-compile-time threshold skips exactly the
many-small-programs workload a bucketed serve engine produces.

Where the cache lives is decided OUTSIDE the program:

- ``JAX_COMPILATION_CACHE_DIR=<dir>`` set: jax itself applies it and no
  code here touches ``jax_compilation_cache_dir`` — the cache is exactly
  ``<dir>``, no sub-directory appended.  The cache key covers the jax
  version and the backend, so one directory serves any mix of both;
  size limits are jax's own (``JAX_COMPILATION_CACHE_MAX_SIZE``).
- unset: ``<checkout>/.jax_cache`` — a fixed path next to the package
  (the path is part of what makes a later process hit), never a temp
  dir, a uid, a pid or a time.
- unset and the process is pinned to the CPU (``JAX_PLATFORMS=cpu``, the
  test suite): no persistent cache.  Cached XLA:CPU executables are
  tied to the host's CPU features and buy a test run nothing.

:class:`CompileCacheManager` adds what jax does not: every program is
cached (min-compile-time 0 — bucket programs are individually small but
collectively the whole cold start), cache traffic is visible as
``mxtpu_compile_cache_{hits,misses,puts}`` counters (fed by the
``jax.monitoring`` bridge in ``telemetry/jaxmon.py``) and on /statusz,
and :meth:`snapshot_to` writes a ``metrics.jsonl``-shaped line that
``tools/metrics_report.py`` renders directly.
"""

from __future__ import annotations

import json
import os
import time

__all__ = ["CompileCacheManager", "enable_from_env", "active", "ENV_DIR",
           "DEFAULT_DIR"]

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_active = None


def active():
    """The process-wide manager installed by :func:`enable_from_env`,
    or None."""
    return _active


class CompileCacheManager:
    """Reports on, and tunes, the persistent cache jax resolved.

    :meth:`enable` adopts ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it and points jax at :data:`DEFAULT_DIR` otherwise
    (idempotent, safe before or after backend init).
    """

    def __init__(self):
        self.dir = None
        self.enabled = False

    # -- wiring ------------------------------------------------------------
    def enable(self):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        if not os.environ.get(ENV_DIR):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        self.dir = jax.config.jax_compilation_cache_dir
        os.makedirs(self.dir, exist_ok=True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax memoizes its cache-enabled decision at the FIRST compile
        # of the task; enabling after any jit has run (an embedding
        # process, a test suite) would silently never cache without
        # this reset
        compilation_cache.reset_cache()
        self.enabled = True
        # live introspection: /statusz shows the cache directory,
        # on-disk occupancy and the hit/miss/put traffic counters.  A
        # strong ref is deliberate: the active manager is a process
        # singleton — there is no retire-without-replacement path
        from ..telemetry import statusz

        statusz.register("aot.compile_cache", self.statusz)
        return self

    # -- inspection --------------------------------------------------------
    def stats(self):
        """On-disk occupancy of the cache directory."""
        entries = size = 0
        try:
            names = os.listdir(self.dir)
        except OSError:
            names = []
        for n in names:
            if not n.endswith("-cache"):
                continue
            try:
                size += os.path.getsize(os.path.join(self.dir, n))
                entries += 1
            except OSError:
                continue               # raced with jax's own eviction
        return {"dir": self.dir, "entries": entries, "bytes": size}

    def statusz(self):
        """/statusz provider: on-disk stats plus the
        ``mxtpu_compile_cache_{hits,misses,puts}`` counters collected
        by the jaxmon bridge (zero when telemetry is disabled).  The
        three families are read directly — not via a full registry
        snapshot, which every /statusz render and flight dump would
        pay for all metrics just to extract three values."""
        from .. import telemetry

        out = dict(self.stats(), enabled=self.enabled)
        reg = telemetry.registry()
        for short, help in (("hits", "persistent compile-cache hits"),
                            ("misses", "persistent compile-cache misses"),
                            ("puts", "persistent compile-cache writes")):
            out[short] = reg.counter(f"mxtpu_compile_cache_{short}",
                                     help).labels().value
        return out

    # -- telemetry snapshot ------------------------------------------------
    def snapshot_to(self, path=None):
        """Append one ``metrics.jsonl``-shaped line (the registry
        snapshot schema ``tools/metrics_report.py`` reads) describing
        the cache: on-disk entry/byte gauges plus the
        ``mxtpu_compile_cache_*`` counters collected so far.  Default
        path: ``<cache dir>/cache_stats.jsonl``."""
        from .. import telemetry

        st = self.stats()
        metrics = {
            "mxtpu_compile_cache_dir_entries": {
                "kind": "gauge", "help": "persistent cache entries on disk",
                "label_names": [],
                "samples": [{"labels": {}, "value": st["entries"]}]},
            "mxtpu_compile_cache_dir_bytes": {
                "kind": "gauge", "help": "persistent cache bytes on disk",
                "label_names": [],
                "samples": [{"labels": {}, "value": st["bytes"]}]},
        }
        snap = telemetry.registry().snapshot()
        for name in ("mxtpu_compile_cache_hits", "mxtpu_compile_cache_misses",
                     "mxtpu_compile_cache_puts"):
            if name in snap:
                metrics[name] = snap[name]
        path = path or os.path.join(self.dir, "cache_stats.jsonl")
        with open(path, "a") as f:
            # mxtpu-lint: disable=wall-clock (JSONL record timestamp)
            f.write(json.dumps({"ts": round(time.time(), 3),
                                "metrics": metrics}) + "\n")
        return path


def enable_from_env():
    """The import-time hook (``mxnet_tpu/__init__``): install the
    process-wide manager unless the process is pinned to the CPU with
    no cache directory given.  Reads configuration only — initialises
    no backend.  Returns the manager, or None when the cache stays off."""
    global _active
    import jax

    if not os.environ.get(ENV_DIR) and jax.config.jax_platforms == "cpu":
        return None
    if _active is None:
        _active = CompileCacheManager().enable()
    return _active
