"""AOT startup subsystem: make process restart cheap.

Every program this framework runs is traced and XLA-compiled per shape
bucket; without persistence a restart (preemption recovery, rolling
deploy, elastic reshard) pays the whole trace+compile bill again before
serving its first token — even though sharded checkpointing already
makes the *state* side of recovery fast.  This package is the compile
side of that story, in three layers that compose but work alone:

- :mod:`cache` — jax's persistent compilation cache, enabled at import
  in ``JAX_COMPILATION_CACHE_DIR`` (else ``<checkout>/.jax_cache``; off
  for a CPU-pinned process): XLA compiles become disk reads across
  processes.  ``mxtpu_compile_cache_{hits,misses,puts}`` counters.
- :mod:`export_store` — serialized ``jax.export`` executables behind
  ``MXTPU_AOT_DIR=<dir>``: Python trace+lower of the serve engine's
  bucketed programs and the fused train step becomes a file
  deserialize.  Fingerprint-keyed; stale/corrupt artifacts fall back
  silently to fresh compilation.
- :mod:`warmup` — JSONL manifests of the (kind, bucket) programs live
  traffic actually hit (``MXTPU_WARMUP_MANIFEST=<path>``), replayed by
  ``serve.Engine.warmup()`` before traffic is admitted and pre-baked
  offline by ``tools/aot_warmup.py``.

``tools/startup_bench.py`` measures the result (STARTUP_BENCH.json:
cold vs warm engine-ready time and compile counts); the operational
recipe lives in docs/how_to/startup.md.
"""

from __future__ import annotations

from . import cache, export_store, warmup
from .cache import CompileCacheManager
from .export_store import ExportStore, default_store, digest, fingerprint
from .warmup import ManifestRecorder, load_manifest

__all__ = ["cache", "export_store", "warmup", "CompileCacheManager",
           "ExportStore", "ManifestRecorder", "default_store", "digest",
           "fingerprint", "load_manifest", "enable_from_env"]


def enable_from_env():
    """Apply the env-var wiring (called from ``mxnet_tpu/__init__``):
    the persistent compile cache (:func:`cache.enable_from_env`).  The
    export store and manifests resolve their env vars lazily at use."""
    return cache.enable_from_env()
