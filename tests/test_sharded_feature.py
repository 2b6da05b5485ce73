"""2D (data × model) feature-sharded fast path of the sharded PASSCoDe
solver (DESIGN.md §10) — the engines that shard w and the feature
dimension along ``model`` must agree with serial DCD and with the 1D
replicated-primal path to atol 1e-5 for every loss in the family and for
delayed (stale-τ) rounds; the column-partition splitter must round-trip;
and the new ``dcd_feature_kernel_fits`` VMEM policy must admit the
webspam/kddb-scale shapes both existing policies reject.

Multi-device agreement (data=4 × model=2, including an n % p tail) is
covered by an 8-host-device subprocess, same pattern as
tests/test_sharded_ell.py.
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

from conftest import serial_replay
from repro.core import sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.sharded import _resolve_kernel_mode_feature
from repro.data.sparse import dense_to_ell, ell_column_split
from repro.dist.mesh import (
    dcd_ell_kernel_fits,
    dcd_feature_kernel_fits,
    dcd_feature_kernel_vmem_bytes,
    dcd_kernel_fits,
)


@pytest.fixture(scope="module")
def tiny_ell(tiny):
    return tiny.X_train


@pytest.fixture(scope="module")
def mesh_2d():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)],
    ids=["hinge", "sq", "logistic"],
)
def test_feature_engine_equivalence(tiny_ell, tiny_dense, mesh_2d, loss,
                                    delay_rounds):
    """serial DCD == 1D-ELL == 2D-unfused == 2D-fused, atol 1e-5."""
    kw = dict(epochs=2, block_size=32, delay_rounds=delay_rounds,
              record=False)
    r_1d = sharded_passcode_solve(tiny_ell, loss, **kw)
    r_2d = sharded_passcode_solve(tiny_ell, loss, mesh=mesh_2d, **kw)
    r_fused = sharded_passcode_solve(tiny_ell, loss, mesh=mesh_2d,
                                     use_kernel=True, **kw)
    refs = [r_1d]
    # on one data shard the delayed round folds the same sum as the
    # eager one, so both schedules are serial DCD in the draw order
    serial = serial_replay(tiny_dense, loss, epochs=2, block_size=32,
                           seed=0)
    np.testing.assert_allclose(np.asarray(r_1d.alpha),
                               np.asarray(serial.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_1d.w_hat),
                               np.asarray(serial.w), rtol=1e-5, atol=1e-5)
    for r in (r_2d, r_fused):
        for ref in refs:
            np.testing.assert_allclose(np.asarray(r.alpha),
                                       np.asarray(ref.alpha),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(r.w_hat),
                                       np.asarray(ref.w_hat),
                                       rtol=1e-5, atol=1e-5)
        # per-shard dummy slots + lane padding stitched off the primal
        assert r.w_hat.shape == r_1d.w_hat.shape


def test_feature_converges_and_records_gaps(tiny_ell, hinge, mesh_2d):
    """record/gap_every parity with the 1D solver — the old demo had
    neither."""
    r2 = sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d, epochs=5,
                                block_size=32, gap_every=2)
    r1 = sharded_passcode_solve(tiny_ell, hinge, epochs=5, block_size=32)
    assert r2.gaps.shape == (3,)  # epochs 2, 4 and the final 5
    assert float(r2.gaps[-1]) == pytest.approx(float(r1.gaps[-1]),
                                               rel=1e-4)
    r_long = sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d,
                                    epochs=12, block_size=32)
    assert float(r_long.gaps[-1]) < 0.5


def test_dense_input_takes_feature_path(tiny_dense, hinge, mesh_2d):
    """Dense X on a 2D mesh converts to ELL internally — no dense
    (n, d_pad) device array like the old demo."""
    r2 = sharded_passcode_solve(np.asarray(tiny_dense), hinge,
                                mesh=mesh_2d, epochs=2, block_size=32,
                                record=False)
    r1 = sharded_passcode_solve(tiny_dense, hinge, epochs=2,
                                block_size=32, record=False)
    np.testing.assert_allclose(np.asarray(r2.alpha), np.asarray(r1.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r2.w_hat), np.asarray(r1.w_hat),
                               rtol=1e-5, atol=1e-5)


def test_feature_auto_mode_falls_back_on_cpu(tiny_ell, hinge, mesh_2d):
    use_k, interpret = _resolve_kernel_mode_feature("auto", 128, 15, 32,
                                                    32)
    assert use_k is False and interpret is True
    r = sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d, epochs=2,
                               block_size=32, use_kernel="auto",
                               record=False)
    assert r.w_hat.shape[0] == tiny_ell.n_features


def test_feature_vmem_policy_admits_webspam_scale():
    """The reason the 2D path exists: webspam's d≈16.6M at m=16 fits the
    feature-sharded policy while BOTH 1D policies reject it (the padded
    replicated primal alone exceeds VMEM)."""
    n, p, m = 350_000, 64, 16
    d, k = 16_609_143, 400
    n_loc = -(-n // p)
    k_loc = -(-k // m)
    d_loc = -(-d // m)
    assert not dcd_kernel_fits(n_loc, d)
    assert not dcd_ell_kernel_fits(d)
    assert dcd_feature_kernel_fits(n_loc, k_loc, d_loc)
    # kddb-scale d≈29.9M needs one more doubling of the model axis
    d_kddb = 29_890_095
    assert not dcd_feature_kernel_fits(n_loc, k_loc, -(-d_kddb // m))
    assert dcd_feature_kernel_fits(n_loc, k_loc, -(-d_kddb // (2 * m)))
    # the budget math is monotone in every shape argument
    assert (dcd_feature_kernel_vmem_bytes(n_loc, k_loc, d_loc)
            < dcd_feature_kernel_vmem_bytes(n_loc, k_loc, 2 * d_loc))


# ------------------------------------- column-partition splitter ----


@st.composite
def ragged_matrix_and_shards(draw):
    n = draw(st.integers(min_value=1, max_value=10))
    d = draw(st.integers(min_value=1, max_value=40))
    m = draw(st.integers(min_value=1, max_value=6))
    rng = np.random.default_rng(draw(st.integers(min_value=0,
                                                 max_value=2**31 - 1)))
    dense = rng.standard_normal((n, d)).astype(np.float32)
    keep = rng.random((n, 1)) * rng.random((n, d))
    return np.where(keep > 0.5, dense, 0.0).astype(np.float32), m


@given(case=ragged_matrix_and_shards())
@settings(max_examples=30, deadline=None)
def test_column_split_round_trip(case):
    dense, m = case
    ell = dense_to_ell(dense)
    fse = ell_column_split(ell, m)
    assert fse.n_shards == m and fse.k_loc >= 1
    assert fse.d_loc == -(-dense.shape[1] // m)
    # local ids stay inside [0, d_loc]; padding slots carry value 0
    idx = np.asarray(fse.indices)
    val = np.asarray(fse.values)
    assert idx.max() <= fse.d_loc
    assert np.all(val[idx == fse.d_loc] == 0.0)
    # shard-local ids + shard offsets reconstruct the matrix exactly
    back = np.asarray(fse.to_ell().to_dense())
    np.testing.assert_array_equal(back, dense)
    np.testing.assert_allclose(np.asarray(fse.row_sq_norms()),
                               (dense * dense).sum(axis=1), rtol=1e-6)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data.sparse import dense_to_ell
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    # 102 % 4 != 0: the masked tail padding is on the 2D hot path here
    X = np.asarray(make_dataset("tiny").dense_train())[:102]
    ell = dense_to_ell(X)
    loss = Hinge(C=1.0)
    # equal data-axis size (and seed) => identical update sequences
    mesh1 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    mesh2 = jax.make_mesh((4, 2), ("data", "model"))
    kw = dict(epochs=3, block_size=8, record=False)
    r0 = sharded_passcode_solve(ell, loss, mesh=mesh1, **kw)
    r1 = sharded_passcode_solve(ell, loss, mesh=mesh2, **kw)
    r2 = sharded_passcode_solve(ell, loss, mesh=mesh2, use_kernel=True,
                                **kw)
    a = [np.asarray(r.alpha) for r in (r0, r1, r2)]
    w = [np.asarray(r.w_hat) for r in (r0, r1, r2)]
    assert a[1].shape == (102,)
    assert np.abs(a[1][96:]).sum() > 0  # tail trained, not dropped
    d1 = np.abs(a[0] - a[1]).max()
    d2 = np.abs(w[0] - w[1]).max()
    d3 = np.abs(a[1] - a[2]).max()
    d4 = np.abs(w[1] - w[2]).max()
    assert max(d1, d2, d3, d4) < 1e-5, (d1, d2, d3, d4)
    # delayed rounds stay equivalent between the 2D engines
    kwd = dict(kw, delay_rounds=1)
    r3 = sharded_passcode_solve(ell, loss, mesh=mesh2, **kwd)
    r4 = sharded_passcode_solve(ell, loss, mesh=mesh2, use_kernel=True,
                                **kwd)
    d5 = np.abs(np.asarray(r3.w_hat) - np.asarray(r4.w_hat)).max()
    assert d5 < 1e-5, d5
    print("SUBPROCESS_OK", d1, d2, d3, d4, d5)
""")


def test_multi_device_feature_equivalence_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
