"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, per-module device durations, per-operation
device time, and the idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e
trace holds (``benchmark/fixtures/toy.xplane.pb`` is a real one):

  plane ``/device:TPU:<n>``   line ``XLA Modules``: one event per program
                              run, named ``jit_<fn>(<hash>)``;
                              line ``XLA Ops``: one event per operation,
                              named by its HLO text
  plane ``/host:CPU``         one line per host thread; the program's
                              ``jax.profiler.TraceAnnotation`` spans
                              (``serve.step``, ``serve.prefill``, ...)
                              sit on the ``python3`` line

Device and host events share one clock to within a millisecond or two,
which is enough to name a gap of several milliseconds.
"""

import bisect
import glob
import os
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_OP_RE = re.compile(r"^%([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_KIND_RE = re.compile(r"kind=(k\w+)")


def find_xplane(log_dir):
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        return None
    return max(found, key=os.path.getmtime)


def _span(ev):
    """(start_s, duration_s) of an event; device events carry exact
    picosecond stats, host events nanoseconds."""
    stats = dict(ev.stats)
    if "device_offset_ps" in stats and "device_duration_ps" in stats:
        return stats["device_offset_ps"] * 1e-12, \
            stats["device_duration_ps"] * 1e-12
    return ev.start_ns * 1e-9, ev.duration_ns * 1e-9


def op_label(name):
    """A short stable label for an operation's HLO text:
    ``<op>[:<kind or custom-call target>] <result shape>`` with the
    numeric and ``.remat`` suffixes of the op's name dropped (``fusion.12``,
    ``fusion.27.remat2`` -> ``fusion``),
    so one label sums the same operation over layers and steps."""
    m = _OP_RE.match(name)
    if not m:
        return name[:60]
    base = re.sub(r"(\.(\d+|remat\d*|clone))+$", "", m.group(1))
    target = _TARGET_RE.search(name)
    kind = _KIND_RE.search(name)
    tag = target.group(1) if target else (kind.group(1) if kind else "")
    label = base + (":" + tag if tag else "")
    return label + (" " + m.group(2) if m.group(2) else "")


def load(path):
    """Raw events of a trace file: per device the module and operation
    events, plus every host event, as (name, start_s, duration_s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    key = "modules"
                elif line.name == OPS_LINE:
                    key = "ops"
                else:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name,) + _span(ev))
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name,) + _span(ev))
    return {"devices": devices, "host": host}


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _enclosing(host_spans, t):
    """Name of the innermost host annotation that holds instant ``t``."""
    best = None
    for name, s, d in host_spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "unnamed"


def reduce(raw, window_s, annotations=("serve.", "trainer.", "bench."),
           top=10):
    """The summary the per-layer readers and the result line use.

    ``busy_s``     union of the device's operation intervals, averaged over
                   the devices in the trace
    ``modules``    {``jit_decode``: [device seconds of each run, ...]}
    ``ops``        [(module, label, seconds, is_kernel)] for every operation
                   (``is_kernel``: a Pallas/Mosaic ``tpu_custom_call``)
    ``device_ops`` the ``top`` labels by summed device time
    ``idle_gaps``  the ``top`` longest gaps between operations on the
                   first device, named by the innermost enclosing host
                   annotation whose name starts with one of ``annotations``
    """
    if not raw["devices"]:
        return None
    merged, modules, ops = [], {}, []
    for dev in raw["devices"]:
        merged.append(merge((s, s + d) for _, s, d in dev["ops"]))
        mods = sorted((s, s + d, name.split("(")[0])
                      for name, s, d in dev["modules"])
        starts = [m[0] for m in mods]
        for s, e, short in mods:
            modules.setdefault(short, []).append(e - s)
        for name, s, d in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < mods[i][1]
            ops.append((mods[i][2] if inside else "", op_label(name), d,
                        "tpu_custom_call" in name))
    by_label = {}
    for module, label, d, _ in ops:
        key = f"{module}:{label}" if module else label
        by_label[key] = by_label.get(key, 0.0) + d
    n_dev = len(raw["devices"])
    device_ops = sorted(([k, v / n_dev] for k, v in by_label.items()),
                        key=lambda kv: -kv[1])[:top]
    busy = [sum(e - s for s, e in m) for m in merged]
    gaps = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                   for a, b in zip(merged[0], merged[0][1:])),
                  reverse=True)[:top]
    spans = [h for h in raw["host"] if h[0].startswith(tuple(annotations))]
    idle_gaps = [[_enclosing(spans, mid), gap] for gap, mid in gaps]
    return {"busy_s": sum(busy) / n_dev, "window_s": float(window_s),
            "devices": n_dev, "modules": modules, "ops": ops,
            "device_ops": device_ops, "idle_gaps": idle_gaps}


def module_time(summary, *prefixes):
    """Summed device seconds of the modules whose name starts with one of
    ``prefixes`` (averaged over devices)."""
    total = sum(sum(v) for k, v in summary["modules"].items()
                if k.startswith(prefixes))
    return total / summary["devices"]
