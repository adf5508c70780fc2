"""The rehearsal of an addition: what a later PR brings for a decoder of
another architecture, added to a temporary copy of ``benchmark/`` and
``BENCHMARK.json`` as NEW FILES AND NEW ENTRIES ONLY, passes every test
that walks the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A ``model_config`` PR may edit no file that is there.  So nothing under
``benchmark/tests`` may count the manifest's entries, pin their order or
know a cell, a mix or a configuration that the manifest does not name; this
file fails when one does.  The copy's tests run as a ``pytest`` child on
the copy (they find ``ROOT`` from their own path), selected with ``-k``:
the cases that walk the manifest and the new cell's own, not the Mistral
and ResNet rehearsals again.
"""

import json
import os
import re
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# -- what the later PR brings ---------------------------------------------------

CELL = "toyssm-2l.toyssm-bursty"

FILES = {
    # a configuration of a NEW kind: its cell file, its sizes, its tiny size
    "toyssm_cell.py": '''\
"""A cell of a kind of its own: a decaying state per request, updated token
by token over the mix's schedule, checked against the closed form."""

import time

import numpy as np

import traffic


def build(cfg, seed):
    rng = np.random.default_rng(int(seed))
    return rng.standard_normal((cfg["vocab_size"], cfg["state_size"]))


def run(cell):
    import mxnet_tpu as mx

    cfg, mix = cell["config"], cell["traffic"]
    emb, decay = cell["build"](cfg, cell["seed"]), cfg["decay"]
    loop = traffic.loop(mix, cell["seed"], cell["seconds"], cfg["vocab_size"])
    setup_s = time.perf_counter() - cell["t_process"]
    start = time.perf_counter()
    loop.start(start)
    served = tokens = 0
    worst = 0.0
    for ids, new_tokens, _ in loop.take(float("inf")):
        tic, state = time.perf_counter(), np.zeros(cfg["state_size"])
        for t in ids:
            state = decay * state + emb[t]
        if mx.telemetry.enabled():
            mx.telemetry.tracer().add_complete(
                "toyssm.update", tic, time.perf_counter(), {"tokens": len(ids)})
        weights = decay ** np.arange(len(ids) - 1, -1, -1.0)
        worst = max(worst, float(np.abs(state - weights @ emb[ids]).max()))
        served, tokens = served + 1, tokens + new_tokens
    end = time.perf_counter()
    e2e = {"setup_s": setup_s, "out_tok_s": tokens / (end - start)}
    ctx = {"kind": "toyssm",
           "window": {"start": start, "end": end, "records": []}}
    return worst < 1e-9, served, 0, e2e, ctx
''',
    "configs/toyssm-2l.json": {
        "kind": "toyssm", "source": "none: written by test_additions.py",
        "vocab_size": 128, "state_size": 16, "decay": 0.5, "reduced": {},
        "engine": {"max_model_len": 512}},
    "tests/tiny/configs/toyssm-2l.json": {
        "kind": "toyssm", "vocab_size": 32, "state_size": 4, "decay": 0.5},
    # a cell on a new open-loop mix whose gaps follow a new distribution
    "dists/twopoint.py": '''\
"""A share ``short`` of the gaps a tenth as long as the others, rescaled
so that the n gaps sum to exactly n/rate."""


def at(spec, qs, rate=None):
    vals = [0.1 if q < spec["short"] else 1.0 for q in qs]
    scale = len(vals) / (rate * sum(vals))
    return [v * scale for v in vals]
''',
    "traffic/toyssm-bursty.json": {
        "loop": "open", "who": "nobody: written by test_additions.py",
        "why": "a mix no test knows, with gaps from a distribution no test knows",
        "rate": 4.0, "ramp_s": 2,
        "prompt": {"dist": "loguniform", "min": 16, "max": 256},
        "output": {"dist": "cycle", "values": [8, 16]},
        "gaps": {"dist": "twopoint", "short": 0.4}, "order_seed": 7,
        "check": {"sequences": [[16, 4]]}},
    "tests/tiny/traffic/toyssm-bursty.json": {
        "loop": "open", "rate": 16, "ramp_s": 0.5,
        "prompt": {"dist": "loguniform", "min": 4, "max": 24},
        "output": {"dist": "cycle", "values": [2, 4]},
        "gaps": {"dist": "twopoint", "short": 0.4}, "order_seed": 7,
        "check": {"sequences": [[8, 2]]}},
    # two per-layer metrics, each a reader of its own
    "layer_metrics/toyssm.update_ms_p50.py": '''\
"""Median time of one request's state updates (the cell's own spans)."""

import span_readers


def read(ctx):
    return span_readers.span_ms_percentile(ctx, "toyssm.update", 50)
''',
    "layer_metrics/toyssm.busy_share.py": '''\
"""Device busy time over the traced span; nothing without a trace."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["busy_s"] / tr["window_s"]
''',
}

ENTRIES = {
    "configs": [{
        "name": "toyssm-2l", "source": "none: written by test_additions.py",
        "file": "benchmark/configs/toyssm-2l.json", "reduced": [],
        "why": "a kind no test knows"}],
    "workloads": [{
        "name": CELL, "config": "toyssm-2l", "traffic": "toyssm-bursty",
        "chips": 1, "why": "a cell no test knows, on a mix no test knows"}],
    "per_layer": [
        {"name": "toyssm.update_ms_p50", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "toy state update",
         "moves": "out_tok_s", "workloads": [CELL]},
        {"name": "toyssm.busy_share", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "device", "moves": "out_tok_s",
         "workloads": [CELL]}],
}

# the cases that walk the manifest, and whatever carries a new name in its id
WALKERS = ("manifest or every_file or every_cell_reports or declared "
           "or distributions_and_loops or tiny_file or forbidden_name "
           "or shapes_respect or toyssm")


def _copy_with_the_additions(tmp, leave_out=()):
    """``tmp``/benchmark and ``tmp``/BENCHMARK.json with ``FILES`` and
    ``ENTRIES`` added, no file that was there touched; the program is the
    repo's own, by a link."""
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "mxnet_tpu"), os.path.join(tmp, "mxnet_tpu"))
    for rel, body in FILES.items():
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), rel         # a new file, no edit
        if rel in leave_out:
            continue
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(body if isinstance(body, str) else json.dumps(body))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for section, entries in ENTRIES.items():
        manifest[section] += entries                 # appended, none moved
    for m in manifest["end_to_end"]:                 # the metric it reports
        if m["name"] == "out_tok_s":
            m["workloads"].append(CELL)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def _pytest_on(tmp, select):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    tests = os.path.join(tmp, "benchmark", "tests")
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-k", select, os.path.join(tests, "test_benchmark.py"),
         os.path.join(tests, "test_span_metrics.py")],
        capture_output=True, text=True, env=env, cwd=tmp, timeout=240)


def test_a_new_architecture_is_files_and_entries_only(tmp_path):
    _copy_with_the_additions(str(tmp_path))
    out = _pytest_on(str(tmp_path), WALKERS)
    tail = out.stdout[-6000:] + out.stderr[-2000:]
    assert out.returncode == 0, tail
    passed = set(re.findall(r"^PASSED \S+::(\S+)", out.stdout, re.M))
    for case in (
            f"test_cell_runs_and_prints_contract_keys[{CELL}-0]",
            f"test_cell_runs_and_prints_contract_keys[{CELL}-1]",
            "test_same_requests_at_same_instants_every_seed[toyssm-bursty]",
            "test_reader_with_nothing_to_read_returns_nothing"
            "[toyssm.busy_share]",
            "test_no_window_or_telemetry_off_reads_nothing"
            "[toyssm.update_ms_p50]",
            "test_an_enabled_but_empty_tracer_reads_nothing"
            "[toyssm.update_ms_p50]",
            "test_distributions_and_loops_are_files[dists-names0-at]",
            "test_every_configuration_and_mix_has_its_tiny_file",
            "test_every_file_a_cell_names_exists",
            "test_every_cell_reports_what_its_metrics_move",
            "test_shapes_respect_the_mix_and_the_model",
            "test_the_twelve_span_metrics_are_declared",
            "test_manifest_keys_and_limits"):
        assert case in passed, (case, tail)
    assert " failed" not in out.stdout and " skipped" not in out.stdout, tail


def test_a_missing_tiny_file_says_which_file_to_add(tmp_path):
    missing = "tests/tiny/traffic/toyssm-bursty.json"
    _copy_with_the_additions(str(tmp_path), leave_out=(missing,))
    out = _pytest_on(str(tmp_path), "tiny_file")
    assert out.returncode == 1, out.stdout[-4000:] + out.stderr[-2000:]
    assert "add benchmark/" + missing in out.stdout
