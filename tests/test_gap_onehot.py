"""The duality gap's two sparse products as one-hot MXU passes
(``repro.kernels.gap_onehot``), in interpret mode on the CPU.

Each product must equal XLA's scatter (``rmv``) and gather (``mv``) to
f32 rounding, on R not a multiple of 8, ids at the dummy slot, a column
repeated within and across blocks, a block count not dividing the rows,
the packed ragged layout, and a vmapped multi-task scale; a whole solve
must record the gaps and backward errors of the XLA path; and the pass
is admitted only where the fused engine runs a sparse shard over a
narrow primal (rcv1's d, not news20's).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sharded, sharded_passcode_solve
from repro.core.duals import Hinge, Logistic
from repro.data.sparse import EllMatrix, csr_from_rows
from repro.dist.mesh import lane_pad
from repro.kernels.gap_onehot import (
    R_MAX,
    _block_rows,
    _r_pad,
    gap_onehot_fits,
    onehot_mv,
    onehot_rmv,
)

RCV1_D, NEWS20_D = 47_236, 1_355_191


def _shard(n, k, d, seed=0, *, repeat=False):
    """An (n, k) ELL shard over d features whose last slot is padding
    (id d, value 0), as prepare_solver lays rows out."""
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, d, (n, k)).astype(np.int32)
    if repeat:  # one column in every row, twice in row 0
        cols[:, 0] = d // 2
        cols[0, 1] = d // 2
    vals = rng.standard_normal((n, k)).astype(np.float32)
    cols[:, -1], vals[:, -1] = d, 0.0
    return jnp.asarray(cols), jnp.asarray(vals)


def _xla_rmv(cols, u, d_run):
    return jnp.zeros((d_run,), jnp.float32).at[cols].add(u)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# (n, k, d): R = d_run / 128 = 5 (not a multiple of 8); n past one block
# of rows; k ≥ 128, which the TPU keeps row-major (transposed in VMEM)
CASES = {
    "r_not_mult_8": (300, 9, 600),
    "rows_past_block": (_block_rows(_r_pad(lane_pad(3_001))) + 77, 5, 3_000),
    "row_major_k": (140, 130, 2_000),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_products_match_xla(case):
    n, k, d = CASES[case]
    d_run = lane_pad(d + 1)
    cols, vals = _shard(n, k, d, seed=1, repeat=True)
    a = jnp.asarray(np.random.default_rng(2).standard_normal(n), jnp.float32)
    w = onehot_rmv(cols, vals, d_run, a, interpret=True)
    ref = _xla_rmv(cols, a[:, None] * vals, d_run)
    assert _rel(w, ref) <= 1e-6
    assert float(w[d]) == 0.0  # the dummy slot adds exact zeros
    z = onehot_mv(cols, vals, ref, row_sum=True, interpret=True)
    assert _rel(z, jnp.sum(ref[cols] * vals, axis=1)) <= 1e-6
    # each gathered word is the stored word
    prod = onehot_mv(cols, vals, ref, row_sum=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(prod),
                                  np.asarray(ref[cols] * vals))


def test_products_packed_ragged_layout():
    """The packed slots viewed as (slots / 128, 128): the scatter of
    each slot's value scaled by its 16-slot group's α, and the per-slot
    gathered products."""
    d, n_slots = 900, 128 * 37
    d_run = lane_pad(d + 1)
    rng = np.random.default_rng(3)
    cols = rng.integers(0, d + 1, n_slots).astype(np.int32)
    u = np.where(cols == d, 0.0, rng.standard_normal(n_slots))
    a = rng.standard_normal(n_slots // 16)
    cols, u = jnp.asarray(cols), jnp.asarray(u, jnp.float32)
    a = jnp.asarray(a, jnp.float32)
    w = onehot_rmv(cols.reshape(-1, 128), u.reshape(-1, 128), d_run,
                   a.reshape(-1, 8), interpret=True)
    ref = _xla_rmv(cols, jnp.repeat(a, 16) * u, d_run)
    assert _rel(w, ref) <= 1e-6
    prod = onehot_mv(cols.reshape(-1, 128), u.reshape(-1, 128), ref,
                     row_sum=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(prod).reshape(-1),
                                  np.asarray(ref[cols] * u))


def test_products_vmapped_tasks():
    """K tasks' scales through one vmapped pass, as the multi-task
    pipeline runs it (DESIGN.md §16)."""
    n, k, d = 200, 7, 700
    d_run = lane_pad(d + 1)
    cols, vals = _shard(n, k, d, seed=4)
    A = jnp.asarray(np.random.default_rng(5).standard_normal((3, n)),
                    jnp.float32)
    W = jax.vmap(lambda a: onehot_rmv(cols, vals, d_run, a,
                                      interpret=True))(A)
    Z = jax.vmap(lambda w: onehot_mv(cols, vals, w, row_sum=True,
                                     interpret=True))(W)
    for t in range(3):
        ref = _xla_rmv(cols, A[t][:, None] * vals, d_run)
        assert _rel(W[t], ref) <= 1e-6
        assert _rel(Z[t], jnp.sum(ref[cols] * vals, axis=1)) <= 1e-6


def _ell_rows(n=300, k=10, d=900, seed=6):
    rng = np.random.default_rng(seed)
    ids = np.stack([np.sort(rng.choice(d, k, replace=False))
                    for _ in range(n)]).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    return EllMatrix(ids, vals, d)


def _ragged_rows(n=200, d=700, seed=7):
    rng = np.random.default_rng(seed)
    rows = []
    for length in np.clip(rng.lognormal(np.log(10), 0.8, n), 1, 200):
        c = np.sort(rng.choice(d, int(length), replace=False))
        v = rng.standard_normal(c.size)
        rows.append((c, v / np.linalg.norm(v)))
    return csr_from_rows(rows, d)


def _solve_both(monkeypatch, X, loss, **kw):
    """The same solve with the pass and with XLA's products."""
    got = sharded_passcode_solve(X, loss, use_kernel=True, **kw)
    with monkeypatch.context() as m:
        m.setattr(sharded, "_gap_onehot", lambda *a: False)
        ref = sharded_passcode_solve(X, loss, use_kernel=True, **kw)
    return got, ref


SOLVES = {
    "ell_hinge": (lambda: _ell_rows(), Hinge(C=1.0), {}),
    "ell_logistic_delay": (lambda: _ell_rows(seed=8), Logistic(C=1.0),
                           {"delay_rounds": 1}),
    "ragged_hinge": (lambda: _ragged_rows(), Hinge(C=1.0), {}),
}


@pytest.mark.parametrize("case", sorted(SOLVES))
def test_solve_records_same_gaps(monkeypatch, case):
    """A whole solve records the XLA path's gaps and backward errors to
    f32 rounding: the updates are untouched (α bit-equal), and the
    records differ by the order of w(α)'s additions, well inside 1e-5
    of the zero-start gap g(0) = C·n (the scale the stopping test reads
    them on) and of ‖ŵ‖."""
    make, loss, kw = SOLVES[case]
    X = make()
    got, ref = _solve_both(monkeypatch, X, loss, epochs=4, block_size=16,
                           seed=3, **kw)
    assert got.gap_engine == "pallas-onehot-interpret"
    assert ref.gap_engine == "xla"  # one rule labels and builds the gap
    np.testing.assert_array_equal(np.asarray(got.alpha),
                                  np.asarray(ref.alpha))
    g0 = loss.C * X.n_rows
    assert np.max(np.abs(np.asarray(got.gaps) - np.asarray(ref.gaps))) \
        <= 1e-5 * g0
    scale = np.linalg.norm(np.asarray(ref.w_hat))
    assert np.max(np.abs(np.asarray(got.eps) - np.asarray(ref.eps))) \
        <= 1e-5 * scale


def test_multitask_solve_records_same_gaps(monkeypatch):
    """One-vs-rest heads vmap the pass with the rest of the closure."""
    X = _ell_rows(n=120, k=6, d=400, seed=9)
    y = np.where(np.random.default_rng(10).random((3, X.n_rows)) < 0.5,
                 1.0, -1.0).astype(np.float32)
    got, ref = _solve_both(monkeypatch, X, Hinge(C=1.0), y=y, epochs=3,
                           block_size=16, seed=4)
    assert got.gap_engine == "pallas-onehot-interpret"
    np.testing.assert_array_equal(np.asarray(got.alpha),
                                  np.asarray(ref.alpha))
    assert np.max(np.abs(np.asarray(got.gaps) - np.asarray(ref.gaps))) \
        <= 1e-5 * X.n_rows


def _tiny_ell(d):
    ids = np.array([[0, d - 1], [1, 2]], np.int32)
    return EllMatrix(ids, np.ones((2, 2), np.float32), d)


@pytest.mark.parametrize("d, use_kernel, want", [
    (RCV1_D, True, "pallas-onehot-interpret"),
    (NEWS20_D, True, "xla"),
    (RCV1_D, False, "xla"),
    (RCV1_D, "auto", "xla"),  # the CPU runs the jnp engine
], ids=["rcv1", "news20", "jnp", "cpu-auto"])
def test_admission(d, use_kernel, want):
    setup = sharded.prepare_solver(_tiny_ell(d), Hinge(C=1.0),
                                   use_kernel=use_kernel)
    assert setup.gap_engine == want
    assert sharded.engine_name(setup) in ("ell/pallas-stream-interpret",
                                          "ell/jnp")


def test_admission_rule():
    """On the chip (compiled) the pass serves R = d_run / 128 ≤ R_MAX:
    rcv1's R = 370, never news20's 10,588; the jnp engine and the dense
    layout keep their own products."""
    assert sharded._gap_onehot(True, True, lane_pad(RCV1_D + 1))
    assert not sharded._gap_onehot(True, True, lane_pad(NEWS20_D + 1))
    assert not sharded._gap_onehot(True, False, 1024)
    assert not sharded._gap_onehot(False, True, lane_pad(RCV1_D + 1))
    assert gap_onehot_fits(128 * R_MAX)
    assert not gap_onehot_fits(128 * (R_MAX + 1))
