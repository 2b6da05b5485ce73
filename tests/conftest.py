import os
import sys

# tests must see exactly ONE device (the dry-run alone uses 512);
# keep any user XLA_FLAGS out of the test environment.
os.environ.pop("XLA_FLAGS", None)
# the suite is CPU-only: on a host with a chip, the default backend would
# be the TPU, and the multi-device subprocess tests would contend for it
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# hypothesis is a test-only dependency (pyproject ``test`` extra); on
# hermetic containers without it, register the deterministic fallback
# under the real module names BEFORE test modules import it.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import importlib.util

    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        os.path.join(os.path.dirname(__file__), "_hypothesis_fallback.py"),
    )
    _mod = importlib.util.module_from_spec(_spec)
    sys.modules["hypothesis"] = _mod
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis.strategies"] = _mod.strategies

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.core.duals import Hinge, SquaredHinge  # noqa: E402
from repro.data.synthetic import make_dataset  # noqa: E402


@pytest.fixture(scope="session")
def tiny():
    return make_dataset("tiny")


@pytest.fixture(scope="session")
def tiny_dense(tiny):
    return tiny.dense_train()


@pytest.fixture(scope="session")
def tiny_test_dense(tiny):
    return tiny.dense_test()


@pytest.fixture(scope="session")
def hinge():
    return Hinge(C=1.0)


@pytest.fixture(scope="session")
def sq_hinge():
    return SquaredHinge(C=1.0)
