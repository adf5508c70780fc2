"""Driver-contract regression gate for bench.py.

``python bench.py`` prints one JSON line; a crash (e.g. an internal
trainer-API signature change) costs the chip run that finds it.  These
tests run every benchmark mode in the explicit ``JAX_PLATFORMS=cpu``
tiny-shape mode and assert the contract fields, so the break is caught
in CI instead of on hardware.  (SURVEY.md §4 lists
"no perf regression gates" among the reference's testing gaps to
improve on.)
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env):
    env = dict(os.environ)
    env.update(extra_env)
    # the explicit CPU pin is what lets bench.py run without a TPU
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no JSON line in output: {r.stdout!r}"
    return json.loads(lines[-1])


def _check_contract(rec, metric, unit):
    assert rec["metric"] == metric
    assert rec["unit"] == unit
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    assert rec["platform"] == "cpu"
    # MFU accounting fields.  model_tflops is
    # rounded to 2 decimals and can legitimately be 0.0 on a very slow
    # CI box, so assert presence only; the 3-decimal per-sample FLOPs
    # field is a deterministic analytic count and must be positive.
    assert rec["fwd_gflops_per_sample"] > 0
    assert "model_tflops_per_sec" in rec


@pytest.mark.slow
def test_resnet_bench_contract():
    rec = _run_bench({})
    _check_contract(rec, "resnet50_train_throughput", "images/sec/chip")


@pytest.mark.slow
def test_gpt_bench_contract():
    rec = _run_bench({"BENCH_MODEL": "gpt"})
    _check_contract(rec, "gpt_train_throughput", "tokens/sec/chip")


@pytest.mark.slow
def test_cifar_bench_contract():
    rec = _run_bench({"BENCH_MODEL": "cifar"})
    _check_contract(rec, "cifar_inception_bn_small_train_throughput",
                    "images/sec/chip")


@pytest.mark.slow
def test_xla_cost_analysis_cross_check():
    """XLA's own cost model and the analytic FLOP counter must agree to
    ~15% on the resnet step (guards count_flops against drift)."""
    rec = _run_bench({})
    # CPU cost_analysis is always available: absence of the fields means
    # the lowering plumbing drifted (exactly what this gate exists for)
    assert "xla_step_gflops" in rec, rec
    ratio = rec["xla_step_gflops"] / rec["analytic_step_gflops"]
    assert 0.85 < ratio < 1.3, rec


@pytest.mark.slow
def test_longcontext_bench_contract():
    """tools/longcontext_bench.py emits its JSON
    payload: flash/dense tokens-per-sec + peak-HBM points and the ring
    scaling lane, on the CPU smoke shapes."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "longcontext_bench.py"),
         "--seqs", "256", "--heads", "2", "--head-dim", "32",
         "--ring-seq", "256", "--ring-widths", "1,2"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    pt = payload["points"][0]
    assert pt["flash_tokens_per_sec"] > 0 and pt["dense_tokens_per_sec"] > 0
    assert pt["flash_peak_hbm_gb"] > 0
    ring = payload["ring"]["points"]
    assert [p["sp"] for p in ring] == [1, 2]
    assert all(p["tokens_per_sec"] > 0 for p in ring)


@pytest.mark.slow
def test_decode_bench_contract():
    """tools/decode_bench.py emits decode tokens/sec points for both the
    gpt2-style and llama-style KV-cache decoders on CPU smoke shapes."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "decode_bench.py"),
         "--platform", "cpu", "--layers", "2", "--d-model", "64",
         "--heads", "4", "--vocab", "97", "--prompt", "8",
         "--t1", "4", "--t2", "24", "--batches", "1,2"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert {pt["config"] for pt in payload["points"]}         == {"gpt2", "llama-style/kv1"}
    assert {pt["batch"] for pt in payload["points"]} == {1, 2}
    for pt in payload["points"]:
        assert pt.get("decode_tok_per_sec", 0) > 0             or "decode_error" in pt, pt


@pytest.mark.slow
def test_serve_bench_contract():
    """tools/serve_bench.py (SERVE_BENCH.json)
    emits the serving record on CPU smoke shapes: last line is the
    payload with aggregate tokens/sec, mean TTFT, preemption count,
    the serial-decode speedup, zero silent drops, and complete:true
    (the bench_io contract the watchdog trusts)."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--layers", "2", "--d-model", "64",
         "--heads", "4", "--vocab", "211", "--requests", "12",
         "--concurrency", "4", "--prompt-lens", "8,16,24",
         "--max-new", "8"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    assert payload["tokens_per_sec"] > 0
    assert payload["ttft_ms_mean"] > 0
    assert payload["preemptions"] >= 0
    assert payload["completed"] == 12
    assert payload["dropped_without_rejection"] == 0
    assert payload["speedup_vs_serial"] > 0
    modes = {pt["mode"] for pt in payload["points"]}
    assert modes == {"continuous/closed", "serial/closed"}
    # every serving record carries the telemetry snapshot field (the
    # registry is empty-disabled unless MXTPU_TELEMETRY=1 was exported)
    assert "telemetry" in payload
    assert payload["telemetry"]["enabled"] in (True, False)


@pytest.mark.slow
def test_prefix_bench_contract():
    """tools/serve_bench.py --workload prefix (PREFIX_BENCH.json) emits both prefix-cache acceptance records on
    CPU smoke shapes: the shared-prefix A/B with hit rate > 0.8, a
    >= 2x prefill-compute reduction and byte-identical tokens, and the
    mixed-length A/B with the chunked decode-stall p99 beating the
    whole-prompt one — the exact invariants the serve_prefix watchdog
    gate trusts."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "prefix",
         "--layers", "2", "--d-model", "64", "--heads", "4",
         "--vocab", "211", "--prefixes", "2", "--continuations", "6",
         "--prefix-len", "32", "--suffix-len", "8", "--max-new", "8",
         "--long-prompt", "1024", "--prefill-chunk", "128"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    modes = {pt["mode"] for pt in payload["points"]}
    assert modes == {"shared-prefix", "mixed-len"}
    # the acceptance bars the serve_prefix stage gates on
    assert payload["tokens_identical"] is True
    assert payload["prefix_hit_rate"] > 0.8
    assert payload["prefill_compute_ratio"] >= 2
    assert payload["prefill_tokens_saved"] > 0
    assert payload["stall_improved"] is True
    assert (payload["decode_stall_p99_ms_chunked"]
            < payload["decode_stall_p99_ms_whole"])
    sp = next(pt for pt in payload["points"]
              if pt["mode"] == "shared-prefix")
    assert sp["completed_on"] == sp["completed_off"] == sp["requests"]
    assert sp["prefix_misses"] == 2         # one cold prefill per prefix
    assert "telemetry" in payload


@pytest.mark.slow
def test_sampling_bench_contract():
    """tools/serve_bench.py --workload sampling (SAMPLING_BENCH.json) on the default CPU smoke shapes (the tiny
    2-layer shapes other contracts use make dispatches too cheap for
    spec to win): a mixed-sampling-config batch with ZERO fresh traces
    and greedy rows byte-identical to a greedy-only engine,
    rejection-sampled spec >= 1.25x plain sampling at temperature>0,
    and the spec-on/off token distributions statistically
    indistinguishable — the invariants the serve_sampling watchdog
    gate trusts."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "sampling",
         "--max-new", "64", "--spec-k", "6",
         "--agreement-samples", "128"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    # the acceptance bars the serve_sampling stage gates on
    assert payload["retraces"] == 0
    assert payload["greedy_rows_identical"] is True
    assert payload["logprobs_ok"] is True
    assert payload["sampling_spec_speedup"] >= 1.25
    assert 0 < payload["accept_rate_stochastic"] < 1
    assert abs(payload["agreement_z"]) < 5
    assert "telemetry" in payload


@pytest.mark.slow
def test_offload_bench_contract():
    """tools/serve_bench.py --workload offload (OFFLOAD_BENCH.json) on CPU smoke shapes: with the HBM prefix LRU
    sized to thrash, the host tier recovers the hit rate to >= 0.8 of
    the unconstrained-HBM run, cuts prefill compute >= 2x vs
    offload-off, and every arm (cold, off, on, int8-KV, tp=2) emits
    byte-identical tokens — the invariants the serve_offload watchdog
    gate trusts."""
    env = dict(os.environ)
    # a pre-set host device count (this repo's conftest pins 8; dev
    # shells sometimes pin 1) would make serve_bench skip forcing its
    # own — drop it so the tp=2 arm always runs
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "offload",
         "--layers", "2", "--d-model", "64", "--heads", "4",
         "--kv-heads", "2", "--vocab", "211", "--offload-prefixes", "6",
         "--continuations", "4", "--prefix-len", "48",
         "--suffix-len", "8", "--max-new", "8"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    # the acceptance bars the serve_offload stage gates on
    assert payload["tokens_identical"] is True
    assert payload["hit_rate_recovery"] >= 0.8
    assert payload["prefill_compute_ratio"] >= 2
    assert payload["host_restores"] > 0
    rec = payload["points"][0]
    assert rec["identity"]["int8_on_vs_off"] is True
    assert rec["tp2"] is not None, "tp=2 arm was skipped (no 2nd device)"
    assert rec["identity"]["tp2_on_vs_cold"] is True
    # the off arm really thrashed (discarding is what motivates the
    # tier) and the on arm really parked instead
    assert payload["discarded_tokens_off"] > 0
    assert rec["discarded_tokens_on"] == 0
    assert rec["hit_rate_off"] < rec["hit_rate_on"]
    assert "telemetry" in payload


@pytest.mark.slow
def test_perf_attrib_bench_contract():
    """tools/serve_bench.py --workload perf-attrib (PERF_ATTRIB_BENCH.json) on CPU smoke shapes:
    device-timing sampling on vs off emits byte-identical tokens with
    unchanged AOT fingerprints, records sampled dispatches and a
    populated nonzero-flops cost table, and the off arm records zero
    timings — the invariants the serve_perf watchdog gate trusts."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "perf-attrib",
         "--layers", "2", "--d-model", "64", "--heads", "4",
         "--vocab", "211", "--requests", "12", "--concurrency", "4",
         "--prompt-lens", "8,16,24", "--max-new", "8"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    # the acceptance bars the serve_perf stage gates on
    assert payload["tokens_identical"] is True
    assert payload["fingerprint_identical"] is True
    assert payload["cost_flops_nonzero"] is True
    assert payload["sampled_dispatches"] > 0
    assert "decode" in payload["cost_table_kinds"]
    assert "prefill" in payload["cost_table_kinds"]
    rec = payload["points"][0]
    assert rec["off_sampled_steps"] == 0    # sampling-off is inert
    assert rec["sampled_steps"] > 0
    assert rec["cost_errors"] == 0
    assert "telemetry" in payload


@pytest.mark.slow
def test_lora_bench_contract():
    """tools/serve_bench.py --workload lora (LORA_BENCH.json) on CPU smoke shapes: one multiplexed engine
    serves base + 3 LoRA adapters with zero fresh traces on the
    rotated second pass and token-identical output against per-tenant
    merged-weights engines — the invariants the serve_lora watchdog
    gate trusts."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--backend", "cpu", "--workload", "lora",
         "--layers", "2", "--d-model", "32", "--heads", "4",
         "--vocab", "128", "--requests", "8", "--concurrency", "4",
         "--max-new", "8", "--prompt-lens", "8,12,16",
         "--block-size", "4"],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    # the acceptance bars the serve_lora stage gates on
    assert payload["fresh_traces_second_pass"] == 0
    assert payload["agreement_vs_merged"] >= 0.98
    assert payload["tokens_identical"] is True
    assert payload["lora_adapters"] == 3
    assert payload["mux_overhead_ratio"] > 0
    rec = payload["points"][0]
    assert rec["completed_off"] == 8
    assert rec["completed_mux"] == 8
    assert rec["adapter_slots_used"] == 3
    assert rec["adapter_loads"] >= 3
    assert "telemetry" in payload


@pytest.mark.slow
def test_train_bench_contract(tmp_path):
    """tools/train_bench.py (TRAIN_BENCH.json)
    emits the training-path comparison on a CPU smoke config: both
    modes measured, per-batch dispatch counts showing the O(1)-vs-
    O(num_params) contrast, and complete:true stamped before the final
    record."""
    env = dict(os.environ)
    out = str(tmp_path / "train_bench.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "train_bench.py"),
         "--backend", "cpu", "--layers", "4", "--hidden", "32",
         "--batches", "8", "--epochs", "2", "--json", out],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    assert payload["fused_steps_per_sec"] > 0
    assert payload["unfused_steps_per_sec"] > 0
    assert payload["speedup"] > 0
    # the dispatch contract: fused <= 3 per batch, per-param pays
    # 1 (fwd_bwd) + num_params
    assert payload["fused_dispatches_per_batch"] <= 3
    assert (payload["unfused_dispatches_per_batch"]
            >= payload["num_params"] + 1)
    assert {pt["mode"] for pt in payload["points"]} == {"fused", "per_param"}
    assert "telemetry" in payload
    # the --json artifact matches the printed record
    disk = json.loads(open(out).read())
    assert disk["complete"] is True
    assert disk["fused_steps_per_sec"] == payload["fused_steps_per_sec"]


@pytest.mark.slow
def test_startup_bench_contract(tmp_path):
    """tools/startup_bench.py (STARTUP_BENCH.json) emits the cold-vs-warm restart record on CPU smoke shapes:
    warm engine-ready-time at most half of cold (the ISSUE acceptance
    bar), ZERO fresh traces on the warm start, token parity between the
    two runs, and complete:true stamped before the final record."""
    env = dict(os.environ)
    # a surrounding compile-cache/AOT config must not leak into the
    # bench's own cold/warm dirs
    for k in ("JAX_COMPILATION_CACHE_DIR", "MXTPU_AOT_DIR",
              "MXTPU_WARMUP_MANIFEST"):
        env.pop(k, None)
    out = str(tmp_path / "startup_bench.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "startup_bench.py"),
         "--backend", "cpu", "--json", out],
        capture_output=True, text=True, timeout=540, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("{")][-1])
    assert payload["platform"] == "cpu"
    assert payload["complete"] is True      # stamped BEFORE the print
    assert payload["cold_ready_s"] > 0 and payload["warm_ready_s"] > 0
    assert payload["warm_ready_s"] <= 0.5 * payload["cold_ready_s"], \
        "warm start did not skip enough compilation"
    assert payload["warm_fresh_traces"] == 0
    assert payload["warm_artifact_loads"] > 0
    assert payload["token_parity"] is True
    assert {pt["mode"] for pt in payload["points"]} == {"cold", "warm"}
    cold, warm = payload["points"]
    # the warm child's compiles were all persistent-cache disk hits
    assert warm["cache_misses"] == 0
    assert warm["cache_hits"] > 0
    assert cold["fresh_traces"] == cold["warmup_programs"]
    disk = json.loads(open(out).read())
    assert disk["complete"] is True
    assert disk["warm_ready_s"] == payload["warm_ready_s"]
