"""Test configuration: run everything on the CPU backend with 8 virtual
XLA host devices, so multi-device paths (multi-context executors, model
parallelism, KVStore reduction, mesh sharding) are exercised without TPU
hardware — the rebuild of the reference's N-CPU-contexts testing trick
(tests/python/unittest/test_model_parallel.py, SURVEY.md §4.3)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The suite runs on the CPU: pin it before jax is imported, whatever the
# machine's default is, so the pytest process never takes the chip.
# Tests that need the chip (test_tpu_consistency.py) drive it from a
# child process.
os.environ["JAX_PLATFORMS"] = "cpu"


# -- fast/slow tiers ---------------------------------------------------------
# Default `pytest tests/` is the fast tier (< 5 min, the reference's
# unittest bucket).  `--runslow` / RUN_SLOW=1 adds the example smokes and
# multi-process dist tests (the nightly bucket, tests/nightly/test_all.sh
# analog).
import pytest


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked slow (nightly tier)")


def pytest_collection_modifyitems(config, items):
    run_slow = os.environ.get("RUN_SLOW", "").lower() not in ("", "0", "false")
    if config.getoption("--runslow") or run_slow:
        return
    skip_slow = pytest.mark.skip(reason="slow tier: use --runslow or RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
