"""Exported-executable store: serialized ``jax.export`` programs on disk.

The persistent compile cache (cache.py) removes the *XLA compile* from a
restart; this store removes the *trace + lower*.  An artifact is one
file holding a JSON fingerprint header plus the serialized StableHLO of
an exported program (the serve engine's bucketed prefill/decode bodies,
the fused train step).  A restarted process that finds a matching
artifact deserializes it and compiles ``Exported.call`` — no Python
re-trace of the model — and that compile in turn hits the persistent
cache, because the cold process executed through the very same wrapped
module it saved.

Staleness is fingerprint-keyed, never versioned by hand: the
fingerprint folds in the artifact format, jax version, backend platform
and the caller's own program key (engine ``_spec_key()`` fields, fused
step shapes).  Any mismatch — moved checkpoint, dtype change, jax
upgrade, truncated file — makes :meth:`load` return None and the caller
traces fresh; a stale artifact can delay a start, never corrupt one.

Layout under ``MXTPU_AOT_DIR``::

  <dir>/<label>-<fp16>.jaxexport     # header \\n blob
  <dir>/manifest.jsonl               # warmup manifest (warmup.py)
"""

from __future__ import annotations

import hashlib
import json
import os

import jax

from .. import telemetry

__all__ = ["ExportStore", "fingerprint", "digest", "default_store",
           "ENV_DIR"]

ENV_DIR = "MXTPU_AOT_DIR"
FORMAT = "mxtpu.aot.v1"

_MAGIC = b"MXTPUAOT"


def fingerprint(**fields):
    """Canonical fingerprint dict for an AOT artifact: caller fields
    plus format/jax-version/backend.  Everything must be JSON-stable —
    tuples arrive as lists, which is fine as long as producers and
    consumers build the dict the same way (they share this helper)."""
    fp = {"format": FORMAT, "jax_version": jax.__version__,
          "backend": jax.default_backend()}
    fp.update(fields)
    return fp


def digest(fp):
    """Stable hex digest of a fingerprint dict (artifact file naming,
    manifest ``spec`` stamps)."""
    return hashlib.sha256(
        json.dumps(fp, sort_keys=True, default=str).encode()).hexdigest()


_digest = digest


def _counter(name, help):
    # re-fetched per call (not cached at construction) so stores built
    # before telemetry.enable() still record afterwards
    return telemetry.counter(name, help, ("kind",))


class ExportStore:
    """Directory of fingerprint-keyed serialized executables."""

    def __init__(self, dir):
        self.dir = str(dir)

    def path_for(self, fp, label="program"):
        return os.path.join(self.dir,
                            f"{label}-{_digest(fp)[:16]}.jaxexport")

    # -- write -------------------------------------------------------------
    def save(self, fp, exported, label="program"):
        """Serialize ``exported`` under fingerprint ``fp``; atomic
        rename so a crashed writer cannot leave a torn artifact.
        Returns the path, or None when serialization is unavailable
        (saving is an optimization — never a hard failure)."""
        try:
            blob = exported.serialize()
        except Exception:
            _counter("mxtpu_aot_errors_total",
                     "AOT artifact failures").labels(kind="serialize").inc()
            return None
        os.makedirs(self.dir, exist_ok=True)
        header = json.dumps({"fingerprint": fp}, sort_keys=True).encode()
        path = self.path_for(fp, label)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(_MAGIC + len(header).to_bytes(8, "little"))
                f.write(header)
                f.write(blob)
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            _counter("mxtpu_aot_errors_total",
                     "AOT artifact failures").labels(kind="write").inc()
            return None
        _counter("mxtpu_aot_saves_total",
                 "AOT artifacts written").labels(kind=label).inc()
        return path

    # -- read --------------------------------------------------------------
    def load(self, fp, label="program"):
        """Deserialize the artifact for fingerprint ``fp``.  Returns the
        ``Exported`` or None (missing / stale / corrupt — all silent
        fallbacks to fresh compilation, counted separately)."""
        path = self.path_for(fp, label)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None                       # missing: the common miss
        try:
            if raw[:len(_MAGIC)] != _MAGIC:
                raise ValueError("bad magic")
            n = int.from_bytes(raw[len(_MAGIC):len(_MAGIC) + 8], "little")
            header_end = len(_MAGIC) + 8 + n
            header = json.loads(raw[len(_MAGIC) + 8:header_end])
            # digests, not dict equality: the header round-tripped
            # through JSON (tuples are lists now) — digest() already
            # canonicalizes exactly that
            if digest(header.get("fingerprint", {})) != digest(fp):
                # the 16-hex-digit prefix collided or the file was
                # copied across configs: stale, not corrupt
                _counter("mxtpu_aot_errors_total",
                         "AOT artifact failures").labels(kind="stale").inc()
                return None
            exported = jax.export.deserialize(raw[header_end:])
        except Exception:
            _counter("mxtpu_aot_errors_total",
                     "AOT artifact failures").labels(kind="corrupt").inc()
            return None
        _counter("mxtpu_aot_loads_total",
                 "AOT artifacts loaded").labels(kind=label).inc()
        return exported

    def entries(self):
        """[(path, bytes)] of artifacts currently in the store."""
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        out = []
        for n in sorted(names):
            if n.endswith(".jaxexport"):
                p = os.path.join(self.dir, n)
                try:
                    out.append((p, os.path.getsize(p)))
                except OSError:
                    pass
        return out


def default_store():
    """The env-configured store (``MXTPU_AOT_DIR``), or None.  Resolved
    per call so tests and late exports can flip the env var."""
    d = os.environ.get(ENV_DIR)
    if not d:
        return None
    return ExportStore(d)
