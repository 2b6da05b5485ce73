"""Pod-scale double-async solver (DESIGN.md §13): the equivalence and
staleness spine that makes Hybrid-DCA trustworthy.

Spine invariants, all against executable references:

  * pod-mesh solves at ``pod_delay_rounds=0`` reduce exactly to the
    plain pipelined solver (single pod) and to the serial CoCoA-style
    oracle ``cocoa_pod_solve`` (multi-pod), at atol 1e-5, across
    hinge / squared-hinge / logistic on both the 1-D and 2-D engines;
  * the convergence-vs-staleness sweep (``pod_delay_rounds`` ∈
    {0,1,2,4}) keeps the final duality gap within a bounded factor of
    the synchronous run while the recorded backward error eps =
    ‖w(α) − ŵ‖ grows monotonically with staleness — PASSCoDe's
    perturbed-regularizer claim, run as a check;
  * the pod row-partition splitter round-trips losslessly (hypothesis);
  * warm starts (``alpha0``/``w0``) re-block carried state onto a new
    pod count — the elasticity primitive (see ``test_elastic.py``).

Multi-pod SPMD behaviour ((pod=2, data=1), (pod=2, data=1, model=2),
(2,2,2) with an n % p row tail) runs in an 8-host-device subprocess,
same pattern as the other sharded test files.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import cocoa_pod_solve, sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.data.sparse import (
    dense_to_ell,
    ell_row_partition,
    pod_row_layout,
)
from repro.dist.mesh import pod_merge_policy, solver_mesh_3d

LOSSES = [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)]


@pytest.fixture(scope="module")
def X102(tiny_dense):
    # 102 rows: n % pods and n % p tails are live on every pod layout
    return np.asarray(tiny_dense)[:102]


def _assert_same(r_a, r_b, *, gaps_tol=None):
    np.testing.assert_allclose(np.asarray(r_a.alpha), np.asarray(r_b.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_a.w_hat), np.asarray(r_b.w_hat),
                               rtol=1e-5, atol=1e-5)
    if gaps_tol is not None:
        np.testing.assert_allclose(np.asarray(r_a.gaps),
                                   np.asarray(r_b.gaps), rtol=gaps_tol,
                                   atol=gaps_tol)


# -------------------------------------- delay-0 reduction, single pod ----


@pytest.mark.parametrize("loss", LOSSES, ids=lambda x: type(x).__name__)
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "ell"])
def test_pod1_reduces_to_plain_pipeline_1d(X102, loss, sparse):
    """A (pod=1, data=p) mesh at pod_delay_rounds=0 runs the plain
    pipelined solve's exact update sequence (same draws, same layout)."""
    X = dense_to_ell(X102) if sparse else X102
    kw = dict(epochs=4, block_size=16, seed=3)
    r_plain = sharded_passcode_solve(X, loss, **kw)
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    r_pod = sharded_passcode_solve(X, loss, mesh=mesh, **kw)
    _assert_same(r_pod, r_plain, gaps_tol=1e-4)


@pytest.mark.parametrize("loss", LOSSES, ids=lambda x: type(x).__name__)
def test_pod1_reduces_to_plain_pipeline_2d(X102, loss):
    """Same reduction on the feature-sharded engine: (pod=1, data=1,
    model=1) vs ("data", "model")."""
    kw = dict(epochs=3, block_size=16, seed=3)
    r_2d = sharded_passcode_solve(
        X102, loss, mesh=jax.make_mesh((1, 1), ("data", "model")), **kw)
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    r_pod = sharded_passcode_solve(X102, loss, mesh=mesh, **kw)
    _assert_same(r_pod, r_2d, gaps_tol=1e-4)


@pytest.mark.parametrize("loss", LOSSES, ids=lambda x: type(x).__name__)
def test_oracle_single_pod_matches_spmd(X102, loss):
    """cocoa_pod_solve replays the SPMD pod path serially: at n_pods=1
    the oracle, the pod mesh and the plain pipeline all agree."""
    kw = dict(epochs=4, block_size=16, seed=5)
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    r = sharded_passcode_solve(X102, loss, mesh=mesh, **kw)
    o = cocoa_pod_solve(X102, loss, n_pods=1, **kw)
    np.testing.assert_allclose(np.asarray(r.alpha), np.asarray(o.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r.w_hat), np.asarray(o.w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r.gaps), np.asarray(o.gaps),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(r.eps), np.asarray(o.eps),
                               atol=1e-4)


# ------------------------------------------ convergence vs staleness ----


def test_staleness_sweep_bounded_gap_monotone_eps(X102, sq_hinge):
    """The oracle's convergence-vs-staleness sweep: more in-flight merge
    rounds never shrink the recorded backward error, and even the
    stalest run's final gap stays within a bounded factor of sync."""
    final_gap, mean_eps = {}, {}
    for delay in (0, 1, 2, 4):
        o = cocoa_pod_solve(X102, sq_hinge, n_pods=4, epochs=8,
                            block_size=16, pod_delay_rounds=delay, seed=0)
        final_gap[delay] = float(o.gaps[-1])
        mean_eps[delay] = float(np.mean(np.asarray(o.eps)))
    # sync keeps w == w(α) exactly: eps is float noise only
    assert mean_eps[0] < 1e-4, mean_eps
    for lo, hi in ((0, 1), (1, 2), (2, 4)):
        assert mean_eps[hi] >= mean_eps[lo] - 1e-4, mean_eps
    for delay in (1, 2, 4):
        assert final_gap[delay] <= 20.0 * final_gap[0], final_gap
        assert np.isfinite(final_gap[delay])


def test_delay0_fifo_invariant(X102, sq_hinge):
    """pod_delay_rounds=0 keeps w == w(α) at every record — the merge
    IS the synchronous CoCoA outer round (nothing left in flight)."""
    o = cocoa_pod_solve(X102, sq_hinge, n_pods=3, epochs=6, block_size=16,
                        pod_delay_rounds=0, seed=1)
    assert float(np.max(np.asarray(o.eps))) < 1e-4


# ------------------------------------------------- admission policy ----


def test_pod_delay_needs_pod_axis(X102, sq_hinge):
    with pytest.raises(ValueError, match="pod"):
        sharded_passcode_solve(X102, sq_hinge, epochs=2,
                               pod_delay_rounds=1)


def test_pod_merge_policy_rejections():
    assert pod_merge_policy(2, n_pods=2) == 2
    with pytest.raises(ValueError):
        pod_merge_policy(-1, n_pods=2)
    with pytest.raises(ValueError):
        pod_merge_policy(1, n_pods=0)
    with pytest.raises(ValueError):
        pod_merge_policy(1, n_pods=2, shrink_every=2)
    with pytest.raises(ValueError):
        pod_merge_policy(1, n_pods=2, overlap=True)
    with pytest.raises(ValueError):
        pod_merge_policy(1, n_pods=2, adaptive=True, record=False)


def test_pod_mesh_rejects_shrinking(X102, sq_hinge):
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    with pytest.raises(ValueError):
        sharded_passcode_solve(X102, sq_hinge, mesh=mesh, epochs=2,
                               shrink_every=1)


# ------------------------------------------------------- warm start ----


def test_warm_start_continues_the_solve(X102, sq_hinge):
    """alpha0/w0 resume: two chained 3-epoch pod solves keep converging
    (the second run's final gap beats the first's), and restarting from
    a state reproduces that state's gap at epoch one."""
    mesh = jax.make_mesh((1, 1), ("pod", "data"))
    kw = dict(mesh=mesh, block_size=16, seed=7)
    r1 = sharded_passcode_solve(X102, sq_hinge, epochs=3, **kw)
    r2 = sharded_passcode_solve(X102, sq_hinge, epochs=3,
                                alpha0=np.asarray(r1.alpha),
                                w0=np.asarray(r1.w_hat), **kw)
    assert float(r2.gaps[-1]) < float(r1.gaps[-1])


# ------------------------------------------- row-partition splitter ----


@st.composite
def ragged_matrix_and_pods(draw):
    n = draw(st.integers(min_value=1, max_value=12))
    d = draw(st.integers(min_value=1, max_value=30))
    pods = draw(st.integers(min_value=1, max_value=6))
    rng = np.random.default_rng(draw(st.integers(min_value=0,
                                                 max_value=2**31 - 1)))
    dense = rng.standard_normal((n, d)).astype(np.float32)
    keep = rng.random((n, 1)) * rng.random((n, d))
    return np.where(keep > 0.5, dense, 0.0).astype(np.float32), pods


@given(case=ragged_matrix_and_pods())
@settings(max_examples=30, deadline=None)
def test_pod_row_partition_round_trip(case):
    dense, pods = case
    n, d = dense.shape
    ell = dense_to_ell(dense)
    pse = ell_row_partition(ell, pods)
    assert pse.n_pods == pods and pse.n_rows == n
    assert pse.rows_per_pod >= -(-n // pods)
    # masks cover exactly the valid rows, once each
    rowmap, mask = pod_row_layout(n, pods, pse.rows_per_pod)
    assert mask.sum() == n
    assert np.array_equal(np.sort(rowmap[mask]), np.arange(n))
    assert np.array_equal(np.asarray(pse.row_mask), mask)
    # padding slots are all-padding rows (index d, value 0)
    idx = np.asarray(pse.indices)
    val = np.asarray(pse.values)
    assert np.all(idx[~mask] == d) and np.all(val[~mask] == 0.0)
    # per-pod shards reassemble the matrix exactly
    back = np.asarray(pse.to_ell().to_dense())
    np.testing.assert_array_equal(back, dense)
    np.testing.assert_allclose(
        np.asarray(pse.row_sq_norms())[mask],
        (dense * dense).sum(axis=1)[rowmap[mask]], rtol=1e-6)
    # padded slots take the solver's q←1 convention
    assert np.all(np.asarray(pse.row_sq_norms())[~mask] == 1.0)


def test_pod_row_layout_rejects_lossy():
    with pytest.raises(ValueError):
        pod_row_layout(10, 2, per_pod_rows=4)  # 4 < ceil(10/2): drops rows
    with pytest.raises(ValueError):
        pod_row_layout(10, 0)


# -------------------------------------------- multi-pod (subprocess) ----


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, numpy as np
    from repro.core import cocoa_pod_solve, sharded_passcode_solve
    from repro.core.duals import Hinge, Logistic, SquaredHinge
    from repro.data.synthetic import make_dataset
    from repro.dist.mesh import solver_mesh_3d

    assert len(jax.devices()) == 8
    A = np.asarray
    # 102 % 2 pods = 0 rows of tail at the pod level but 51 % 2 devices
    # leaves a per-pod data tail; 102 also != any pad multiple at p=2
    X = A(make_dataset("tiny").dense_train())[:102]
    kw = dict(epochs=5, block_size=16, seed=0)

    # --- oracle vs SPMD, every loss x delay, (pod=2, data=1) ---------
    mesh21 = jax.make_mesh((2, 1), ("pod", "data"),
                           devices=jax.devices()[:2])
    for loss in (Hinge(1.0), SquaredHinge(1.0), Logistic(1.0)):
        for delay in (0, 1, 2):
            r = sharded_passcode_solve(X, loss, mesh=mesh21,
                                       pod_delay_rounds=delay, **kw)
            o = cocoa_pod_solve(X, loss, n_pods=2,
                                pod_delay_rounds=delay, **kw)
            np.testing.assert_allclose(A(r.alpha), A(o.alpha),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(A(r.w_hat), A(o.w),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(A(r.gaps), A(o.gaps), rtol=2e-3,
                                       atol=1e-4)
            np.testing.assert_allclose(A(r.eps), A(o.eps), atol=1e-3)

    # --- 2D engine under pods: (pod=2, data=1, model=2) vs oracle ----
    loss = SquaredHinge(1.0)
    mesh212 = solver_mesh_3d(pod=2, data=1, model=2,
                             n_devices=4)
    for delay in (0, 1):
        r = sharded_passcode_solve(X, loss, mesh=mesh212,
                                   pod_delay_rounds=delay, **kw)
        o = cocoa_pod_solve(X, loss, n_pods=2, pod_delay_rounds=delay,
                            **kw)
        np.testing.assert_allclose(A(r.alpha), A(o.alpha),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(A(r.w_hat), A(o.w),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(A(r.gaps), A(o.gaps), rtol=2e-3,
                                       atol=1e-4)

    # --- 8 devices: (2,2,2) matches (2,2) -- the model split is free -
    mesh22 = jax.make_mesh((2, 2), ("pod", "data"),
                           devices=jax.devices()[:4])
    mesh222 = solver_mesh_3d(pod=2, data=2, model=2)
    for delay in (0, 1):
        r2 = sharded_passcode_solve(X, loss, mesh=mesh22,
                                    pod_delay_rounds=delay, **kw)
        r3 = sharded_passcode_solve(X, loss, mesh=mesh222,
                                    pod_delay_rounds=delay, **kw)
        np.testing.assert_allclose(A(r3.alpha), A(r2.alpha),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(A(r3.w_hat), A(r2.w_hat),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(A(r3.gaps), A(r2.gaps), rtol=2e-3,
                                   atol=1e-4)

    # --- SPMD staleness sweep: monotone recorded eps -----------------
    eps_mean = []
    for delay in (0, 1, 2, 4):
        r = sharded_passcode_solve(X, loss, mesh=mesh22, epochs=8,
                                   block_size=16, seed=0,
                                   pod_delay_rounds=delay)
        eps_mean.append(float(np.mean(A(r.eps))))
    assert eps_mean[0] < 1e-4, eps_mean
    assert all(b >= a - 1e-4 for a, b in zip(eps_mean, eps_mean[1:])), \\
        eps_mean
    print("POD_OK", eps_mean)
""")


def test_multi_pod_matches_oracle_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "POD_OK" in out.stdout


def test_solver_mesh_3d_shapes():
    mesh = solver_mesh_3d(pod=1, data=1, model=1, n_devices=1)
    assert mesh.axis_names == ("pod", "data", "model")
    assert mesh.shape["pod"] == 1
