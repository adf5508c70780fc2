"""The on-chip smoke (``chip_smoke.py``), debugged where chip time is free.

Two things are pinned on the CPU: without a TPU the script refuses to run
(non-zero exit, names the missing TPU, prints no result line), and its two
phase functions run end to end at a tiny size — interpret-mode kernels, a
two-layer decoder, a small ResNet — so the control flow, the manifest
arithmetic and every check are exercised before a chip minute is spent.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

import mxnet_tpu as mx  # noqa: E402

# the full configuration's shape at toy widths: every prompt class and
# every bucket family of FULL_SERVE has its counterpart here
TINY_SERVE = dict(
    vocab=97, d_model=64, num_heads=4, kv_heads=2, d_ff=128, num_layers=2,
    max_model_len=128, block_size=4, num_blocks=257, max_batch=8,
    prefill_chunk=64, dtype="float32", max_new=16,
    whole=64, chunked=88, prefix=8, suffix=6, short=20)
# the space-to-depth stem needs >= 64 px; ResNet-18 keeps the compile short
TINY_TRAIN = dict(num_layers=18, image_hw=64, batch_per_chip=1,
                  dtype="float32", steps=2)


@pytest.fixture
def tel():
    mx.telemetry.reset()
    mx.telemetry.enable()
    yield
    mx.telemetry.disable()
    mx.telemetry.reset()


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""        # no result line of any kind


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ``ok`` and
    ``device`` {platform, kind, count}; the report goes on the line before."""
    import json

    import jax

    line = chip_smoke.verdict_line(jax.devices())
    assert "\n" not in line
    verdict = json.loads(line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    dev = verdict["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == jax.devices()[0].platform
    assert dev["kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices()) and type(dev["count"]) is int


def test_full_serve_config_meets_the_floors():
    c = chip_smoke.FULL_SERVE
    head_dim = c["d_model"] // c["num_heads"]
    assert head_dim == 128 and c["kv_heads"] < c["num_heads"]
    assert c["d_model"] >= 2048 and c["vocab"] >= 32768
    assert c["num_layers"] >= 8 and c["max_model_len"] >= 4096
    assert c["dtype"] == "bfloat16" and c["max_batch"] >= 8
    assert c["max_new"] >= 64 and c["whole"] >= 2048
    assert c["whole"] <= c["prefill_chunk"] < c["chunked"]
    assert c["chunked"] + c["max_new"] <= c["max_model_len"]
    per_layer = (2 * c["d_model"] ** 2
                 + 2 * c["d_model"] * c["kv_heads"] * head_dim
                 + 3 * c["d_model"] * c["d_ff"])
    weights = 2 * (c["num_layers"] * per_layer
                   + 2 * c["vocab"] * c["d_model"])
    cache = (2 * 2 * c["num_layers"] * c["num_blocks"] * c["block_size"]
             * c["kv_heads"] * head_dim)
    assert weights + cache >= 8e9


def test_serve_phase_tiny(tel, monkeypatch):
    """The serve phase on CPU with the Pallas paged kernel forced (it runs
    interpreted here), numeric watch on as ``main()`` sets it."""
    monkeypatch.setenv("MXTPU_PAGED_ATTENTION", "pallas")
    monkeypatch.setenv("MXTPU_NUMERIC_WATCH", "1")
    rep = chip_smoke.serve_phase(TINY_SERVE)
    assert rep["paged_attention"] == "pallas"
    assert rep["requests"] == 9 and len(rep["tokens"]) == 9
    assert rep["compiles_after_warmup"] == 0
    assert rep["prefix_hits"] >= 3
    assert rep["kernel_max_abs_err"] <= rep["kernel_tol"]
    # heads of 16 off the chip: the dense branch, against the same oracle
    assert rep["span_attention"] == "dense"
    assert rep["span_max_abs_err"] <= rep["span_tol"]
    assert set(rep["programs"]) >= {"serve.decode8", "serve.prefill64",
                                    "serve.chunk64", "serve.chunk8"}


def test_train_phase_tiny(tel):
    rep = chip_smoke.train_phase(TINY_TRAIN)
    assert rep["dp"] == 8 and rep["compiles_after_step1"] == 0
    assert len(rep["losses"]) == TINY_TRAIN["steps"]


def test_f64_audit_rejects_nonscalar_f64_only():
    audit = {}
    chip_smoke._audit_program_text(
        "ok", "%0 = f64[] constant(1)\n%1 = tensor<f64>\n%2 = s64[4]", audit)
    assert audit["ok"]["i64_values"] == 1
    for bad in ("%0 = f64[8,16] add(...)", "tensor<8x16xf64>"):
        with pytest.raises(AssertionError):
            chip_smoke._audit_program_text("bad", bad, {})
