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
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.core.dcd import DcdState, dcd_epoch  # noqa: E402
from repro.core.duals import Hinge, SquaredHinge  # noqa: E402
from repro.core.sharded import _device_block_perm, _n_blocks  # noqa: E402
from repro.data.sparse import CsrMatrix, EllMatrix  # noqa: E402
from repro.data.synthetic import make_dataset  # noqa: E402


def serial_replay(X, loss, epochs, block_size, seed, p=1):
    """The serial oracle of the sharded solve: ``dcd_epoch`` replaying
    the solver's own update sequence — the PRNG chain split once per
    epoch, and each device's masked block draw (``_device_block_perm``)
    over its ``ceil(n / p)`` rows, padding rows included.

    At ``p = 1`` this is serial DCD in that order.  At ``p > 1`` it is
    the round-synchronous schedule of the round scan: each round, every
    device runs its block from the round's starting w, and the Δw are
    summed.  ``X`` is a dense array, an ``EllMatrix`` or a
    ``CsrMatrix`` (run as its ELL padding).  Returns ``DcdState``
    (α over the n real rows, w over d)."""
    if isinstance(X, CsrMatrix):
        X = X.to_ell()
    ell = isinstance(X, EllMatrix)
    n, d = (X.n_rows, X.n_features) if ell else np.shape(X)
    n_loc = -(-n // p)
    n_pad = p * n_loc
    n_blocks = _n_blocks(n_loc, block_size)
    # padding rows are zero rows with ‖x‖² = 1, as the solver pads them
    sq = np.ones((n_pad,), np.float32)
    if ell:
        sq[:n] = np.asarray(X.row_sq_norms())
        cols = np.full((n_pad, X.k_max), d, np.int32)
        vals = np.zeros((n_pad, X.k_max), np.float32)
        cols[:n], vals[:n] = X.indices, X.values
        X = EllMatrix(jnp.asarray(cols), jnp.asarray(vals), d)
    else:
        rows = np.zeros((n_pad, d), np.float32)
        rows[:n] = np.asarray(X)
        sq[:n] = (rows[:n] * rows[:n]).sum(axis=1)
        X = jnp.asarray(rows)
    sq = jnp.asarray(sq)
    state = DcdState(jnp.zeros((n_pad,), jnp.float32),
                     jnp.zeros((d,), jnp.float32))
    key = jax.random.PRNGKey(seed)
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        order = [j * n_loc + _device_block_perm(sub, j, p, n_loc, n,
                                                n_blocks, block_size)
                 for j in range(p)]
        if p == 1:
            state = dcd_epoch(X, sq, state, order[0].reshape(-1), loss)
            continue
        for r in range(n_blocks):
            alpha, dw = state.alpha, jnp.zeros_like(state.w)
            for j in range(p):
                out = dcd_epoch(X, sq, DcdState(alpha, state.w),
                                order[j][r], loss)
                alpha, dw = out.alpha, dw + (out.w - state.w)
            state = DcdState(alpha, state.w + dw)
    return DcdState(state.alpha[:n], state.w)


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
