#!/usr/bin/env python3
"""The benchmark's command: one run of one cell, one JSON line.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1

Everything that belongs to one cell is data the run finds by the names
in ``BENCHMARK.json``: ``benchmark/configs/<config>.json`` (sizes, source,
cuts, builder arguments), ``benchmark/traffic/<traffic>.json`` (the mix's
parameters, read by the one generator in ``traffic.py``) and, for every
per-layer metric the cell reports, ``benchmark/layer_metrics/<name>.py``
(one ``read(ctx)``).  ``<kind>_cell.py`` runs a configuration of that
``kind``; ``benchmark/configs/<config>.py``, if present, replaces the
kind's ``build``.

No TPU, no run: the command exits non-zero and prints no result unless
jax reports platform ``tpu`` and as many devices as the cell's ``chips``.
Standard output carries ``{"info": ...}`` lines (sample counts, set-up
phases, the check's verdict) and, LAST, the result line.
"""

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 4.0


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest, workload):
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


def load_cell(workload):
    """(manifest, the cell's row, its configuration, its traffic mix)."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    row = find_cell(manifest, workload)
    cfg_row = next(c for c in manifest["configs"] if c["name"] == row["config"])
    return (manifest, row, load_json(ROOT, cfg_row["file"]),
            load_json(HERE, "traffic", row["traffic"] + ".json"))


def metric_names(manifest, section, workload):
    """Names of the section's metrics this cell reports."""
    return [m["name"] for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


class CompileCounter:
    """Programs handed to the XLA compiler so far, persistent-cache hits
    included: the benchmark's own listener on jax's monitoring event (the
    program's counter needs its telemetry switched on)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1

    def __call__(self):
        return self.n


class Tracer:
    """``jax.profiler`` over the last ``seconds`` of the window: started
    between two steps, stopped after the window has closed, so the stop
    (seconds of writing) costs the window nothing.  The trace goes to a
    temporary directory that is removed after the reduction."""

    def __init__(self, seconds):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="benchtrace-")
        self.started = self.stopped = False
        self.t_start = self.t_stop = None

    def _options(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.host_tracer_level = 2       # TraceAnnotation spans
        opts.enable_hlo_proto = False    # no per-program HLO serialisation
        return opts

    def prewarm(self):
        """Pay the profiler's cold start (seconds) during set-up."""
        import jax

        jax.profiler.start_trace(os.path.join(self.dir, "warm"),
                                 profiler_options=self._options())
        jax.profiler.stop_trace()

    def due(self, now, end):
        return not self.started and now >= end - self.seconds

    def maybe_start(self, now, end):
        if self.due(now, end):
            import jax

            jax.profiler.start_trace(os.path.join(self.dir, "run"),
                                     profiler_options=self._options())
            self.started = True
            self.t_start = time.perf_counter()

    def stop(self):
        if self.started and not self.stopped:
            import jax

            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped = True

    def summary(self):
        import trace_reduce

        if not self.stopped:
            return None
        path = trace_reduce.find_xplane(os.path.join(self.dir, "run"))
        if path is None:
            return None
        return trace_reduce.reduce(trace_reduce.load(path),
                                   self.t_stop - self.t_start)

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def info(**fields):
    print(json.dumps({"info": fields}), flush=True)


def run_cell(manifest, workload, config, mix, seed, seconds, trace,
             peaks=None, trace_seconds=TRACE_SECONDS):
    """One run; returns the result object.  ``main`` has already refused
    to come here without the chips; the tests come here with tiny
    dictionaries on the CPU, and the result says so under ``device``."""
    import jax

    cell_row = find_cell(manifest, workload)
    kind = importlib.import_module(config["kind"] + "_cell")
    build = kind.build
    override = os.path.join(HERE, "configs", cell_row["config"] + ".py")
    if os.path.exists(override):
        build = load_module(override, "config_build").build
    tracer = None
    if trace:
        import mxnet_tpu as mx

        mx.telemetry.enable()            # the program's spans enter the trace
        tracer = Tracer(min(trace_seconds, seconds / 2.0))
        tracer.prewarm()
    cell = {"config": config, "traffic": mix, "seed": int(seed),
            "seconds": float(seconds), "tracer": tracer,
            "compiles": CompileCounter(), "info": info,
            "t_process": T_PROCESS, "chips": cell_row["chips"],
            "build": build}
    try:
        correct, attempted, failed, e2e, ctx = kind.run(cell)
        summary = tracer.summary() if tracer else None
    finally:
        if tracer:
            tracer.close()
    devs = jax.local_devices()
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(
                  peak_mem + ctx.get("program_temp_bytes", 0))}
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": {}, "device": device}
    if not trace:
        values = {n: e2e.get(n)
                  for n in metric_names(manifest, "end_to_end", workload)}
    else:
        ctx.update(trace=summary, peaks=peaks, config=config, traffic=mix,
                   end_to_end=e2e,
                   trace_span=(tracer.t_start, tracer.t_stop))
        values = {}
        for name in metric_names(manifest, "per_layer", workload):
            reader = load_module(
                os.path.join(HERE, "layer_metrics", name + ".py"),
                "layer_metric")
            values[name] = reader.read(ctx)
        if summary:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    for name, value in values.items():
        if value is None:
            continue                     # nothing to read: left out
        if value != value or value in (float("inf"), float("-inf")):
            raise SystemExit(f"run.py: {name} is {value}: more requests "
                             "failed than the percentile leaves out")
        result["metrics"][name] = {"value": float(value), "unit": units[name]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, row, config, mix = load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: no TPU: jax reports platform "
                 f"{devs[0].platform!r}; nothing is measured on it")
    if len(devs) != row["chips"]:
        sys.exit(f"run.py: {args.workload} needs {row['chips']} chip(s), "
                 f"jax reports {len(devs)}")
    import arith
    import mxnet_tpu as mx

    peaks = arith.peaks(devs[0].device_kind)     # unlisted kind: an error
    if mx.aot.cache.active() is None:
        sys.exit("run.py: the persistent compile cache is off")
    info(workload=args.workload, seed=args.seed, seconds=args.seconds,
         trace=args.trace, device=devs[0].device_kind, chips=len(devs),
         compile_cache=jax.config.jax_compilation_cache_dir)
    result = run_cell(manifest, args.workload, config, mix, args.seed,
                      args.seconds, args.trace, peaks=peaks)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
