"""On-device multi-epoch pipeline of the sharded PASSCoDe solver
(DESIGN.md §11): the single-dispatch solve must run the update sequence
of the serial oracle (``conftest.serial_replay``: ``dcd_epoch`` fed the
solver's own per-device block draws) — alpha/w must agree to atol 1e-5
for every loss × delay_rounds × engine on the 1-D mesh and on the 2-D
mesh, the per-device draw must cycle a permutation of the device's valid
rows, and the on-device duality-gap buffer must hold the oracle's gaps
on the ``gap_every`` schedule.  The double-buffered fused 2-D round
(``overlap``) must agree with the oracle too.

Also the regression tests for this PR's silent-data-loss fixes:
``dense_to_ell`` must raise on a lossy ``k_max`` instead of truncating
rows, and an epoch must visit every valid row when ``block_size`` does
not divide the device-local row count (the old floor'd block count
silently skipped up to B−1 rows per device per epoch).

Multi-device behaviour (n % p tail, 4×2 mesh, fused overlap) runs in an
8-host-device subprocess, same pattern as the other sharded test files.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from conftest import serial_replay
from repro.core import sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.objective import duality_gap
from repro.core.sharded import _device_block_perm, _gap_slots, _n_blocks
from repro.data.sparse import dense_to_ell


@pytest.fixture(scope="module")
def tiny_ell(tiny):
    return tiny.X_train


@pytest.fixture(scope="module")
def mesh_2d():
    return jax.make_mesh((1, 1), ("data", "model"))


def _assert_matches(r, ref, X=None, loss=None):
    """(α, ŵ) equal to the oracle's at atol 1e-5; with ``X``, the last
    recorded gap equal to the oracle's gap (f32 sums in another order)."""
    np.testing.assert_allclose(np.asarray(r.alpha), np.asarray(ref.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r.w_hat), np.asarray(ref.w),
                               rtol=1e-5, atol=1e-5)
    if X is not None:
        g = float(duality_gap(ref.alpha, X, loss))
        np.testing.assert_allclose(float(r.gaps[-1]), g, rtol=1e-3,
                                   atol=1e-3)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "pallas-interpret"])
@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)],
    ids=["hinge", "sq", "logistic"],
)
def test_pipeline_matches_serial_1d(tiny_ell, loss, delay_rounds,
                                    use_kernel):
    """Single-dispatch solve == serial DCD in its draw order, 1-D ELL
    path, on the jnp engine and the streamed kernel."""
    r = sharded_passcode_solve(tiny_ell, loss, epochs=2, block_size=32,
                               delay_rounds=delay_rounds,
                               use_kernel=use_kernel)
    ref = serial_replay(tiny_ell, loss, epochs=2, block_size=32, seed=0)
    _assert_matches(r, ref, tiny_ell, loss)


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)],
    ids=["hinge", "sq", "logistic"],
)
def test_pipeline_matches_serial_2d(tiny_ell, mesh_2d, loss, delay_rounds):
    """Single-dispatch solve == serial DCD in its draw order, 2-D mesh."""
    r = sharded_passcode_solve(tiny_ell, loss, mesh=mesh_2d, epochs=2,
                               block_size=32, delay_rounds=delay_rounds)
    ref = serial_replay(tiny_ell, loss, epochs=2, block_size=32, seed=0)
    _assert_matches(r, ref, tiny_ell, loss)


def test_pipeline_matches_serial_dense(tiny_dense, hinge):
    """The dense 1-D engine pipelines too (X.T@α / X@w gap path)."""
    r = sharded_passcode_solve(tiny_dense, hinge, epochs=2, block_size=32)
    ref = serial_replay(tiny_dense, hinge, epochs=2, block_size=32, seed=0)
    _assert_matches(r, ref, tiny_dense, hinge)


def test_overlap_agrees_with_unfused(tiny_ell, hinge, mesh_2d):
    """The double-buffered fused round — stale base⁰ + Gram carried in
    flight, base repaired by ``dcd_feature_base_correction`` — is the
    update sequence of the eager per-update-psum engine and of the
    serial oracle."""
    kw = dict(mesh=mesh_2d, epochs=2, block_size=32, delay_rounds=1,
              record=False)
    r_eager = sharded_passcode_solve(tiny_ell, hinge, **kw)
    r_ov = sharded_passcode_solve(tiny_ell, hinge, use_kernel=True,
                                  overlap=True, **kw)
    ref = serial_replay(tiny_ell, hinge, epochs=2, block_size=32, seed=0)
    _assert_matches(r_eager, ref)
    _assert_matches(r_ov, ref)


def test_overlap_knob_validation(tiny_ell, hinge, mesh_2d):
    """overlap=True outside its domain raises instead of silently
    changing semantics; the "auto" default never does."""
    with pytest.raises(ValueError):  # 1-D mesh: no model psum
        sharded_passcode_solve(tiny_ell, hinge, epochs=1, overlap=True,
                               delay_rounds=1, use_kernel=True)
    with pytest.raises(ValueError):  # unfused: no split phases
        sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d, epochs=1,
                               overlap=True, delay_rounds=1)
    with pytest.raises(ValueError):  # eager rounds: no carried aggregate
        sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d, epochs=1,
                               overlap=True, use_kernel=True)
    r = sharded_passcode_solve(tiny_ell, hinge, mesh=mesh_2d, epochs=1,
                               block_size=64, record=False)  # auto: fine
    assert r.w_hat.shape[0] == tiny_ell.n_features


def test_device_perm_cycles_valid_rows():
    """Each device's in-body draw cycles a permutation of its valid rows
    — including devices whose shard is partly or entirely padding — so
    padding rows are never drawn where the device owns a real row; with
    no padding it is the plain ``permutation(n_loc)`` of the device's
    key, cycled to n_blocks·B slots."""
    for p, n_loc, n_rows, n_blocks, B in ((4, 26, 102, 4, 8),
                                          (1, 256, 256, 8, 32),
                                          (4, 8, 9, 2, 4)):  # dev 2+: pad
        key = jax.random.PRNGKey(7)
        m = n_blocks * B
        for my in range(p):
            got = np.asarray(_device_block_perm(
                key, my, p, n_loc, n_rows, n_blocks, B)).reshape(-1)
            assert got.shape == (m,)
            real = min(max(n_rows - my * n_loc, 0), n_loc)
            v = max(real, 1)  # a padding-only device draws its row 0
            first = got[:min(v, m)]
            assert len(set(first.tolist())) == first.size
            assert set(first.tolist()) <= set(range(v))
            np.testing.assert_array_equal(got, got[np.arange(m) % v])
            if real:
                assert got.max() < real  # no padding row drawn
            if real == n_loc:
                perm = np.asarray(jax.random.permutation(
                    jax.random.split(key, p)[my], n_loc))
                np.testing.assert_array_equal(got,
                                              perm[np.arange(m) % n_loc])


def test_gap_buffer_honors_gap_every(tiny_ell, hinge):
    """Gaps accumulate into the preallocated on-device buffer on the
    ``gap_every`` schedule — every ``gap_every``-th epoch + the final —
    and each is the oracle's gap after that many epochs."""
    assert _gap_slots(5, 2) == 3 and _gap_slots(4, 2) == 2
    assert _gap_slots(3, 10) == 1 and _gap_slots(0, 1) == 0
    r = sharded_passcode_solve(tiny_ell, hinge, epochs=5, block_size=32,
                               gap_every=2)
    assert r.gaps.shape == (3,)  # epochs 2, 4 and the final 5
    want = [float(duality_gap(
        serial_replay(tiny_ell, hinge, epochs=e, block_size=32,
                      seed=0).alpha, tiny_ell, hinge)) for e in (2, 4, 5)]
    np.testing.assert_allclose(np.asarray(r.gaps), want, rtol=1e-3)
    r_off = sharded_passcode_solve(tiny_ell, hinge, epochs=2,
                                   block_size=32, record=False)
    assert r_off.gaps.shape == (0,)


# ------------------------------------------- silent-data-loss fixes ----


def test_dense_to_ell_raises_on_lossy_k_max():
    """Regression: a too-small ``k_max`` used to silently truncate rows
    (``cols[:k_max]``) — corrupted X, no error.  Now it raises like
    ``ell_column_split`` always did."""
    rng = np.random.default_rng(0)
    dense = np.where(rng.random((8, 32)) > 0.6, 1.0, 0.0).astype(np.float32)
    need = int((dense != 0).sum(axis=1).max())
    with pytest.raises(ValueError):
        dense_to_ell(dense, k_max=need - 1)
    for k in (need, need + 3):  # exact and padded both round-trip
        ell = dense_to_ell(dense, k_max=k)
        assert ell.k_max == k
        np.testing.assert_array_equal(np.asarray(ell.to_dense()), dense)


def test_epoch_visits_every_row():
    """Regression: with ``block_size ∤ n_loc`` the floor'd block count
    skipped up to B−1 rows per device per epoch — an "epoch" was not a
    full pass.  Orthogonal rows make coverage visible: wᵀx_i stays 0 for
    unvisited rows, so after one epoch α_i > 0 iff row i was selected."""
    assert _n_blocks(10, 4) == 3 and _n_blocks(8, 4) == 2
    assert _n_blocks(3, 64) == 1
    X = 0.5 * np.eye(10, dtype=np.float32)
    r = sharded_passcode_solve(X, Hinge(C=1.0), epochs=1, block_size=4,
                               record=False)
    assert (np.asarray(r.alpha) > 0).all(), np.asarray(r.alpha)
    # the ceil'd draw cycles valid rows instead of dropping them
    perm = _device_block_perm(jax.random.PRNGKey(0), 0, 1, 10, 10,
                              _n_blocks(10, 4), 4)
    assert set(np.asarray(perm).ravel()) == set(range(10))


# ------------------------------------------------- multi-device case ----


_SUBPROCESS = textwrap.dedent("""
    import os
    import sys
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {tests!r})
    # the test suite's conftest clears XLA_FLAGS on import: set them
    # after it, before the first device query
    from conftest import serial_replay
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data.sparse import dense_to_ell
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    # 102 % 4 != 0 (row tail) and 26 % 8 != 0 (block tail): both masked
    # paths are hot in the pipelined in-body draws
    X = np.asarray(make_dataset("tiny").dense_train())[:102]
    ell = dense_to_ell(X)
    loss = Hinge(C=1.0)
    mesh1 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    mesh2 = jax.make_mesh((4, 2), ("data", "model"))
    A = lambda r: (np.asarray(r.alpha), np.asarray(r.w_hat),
                   np.asarray(r.gaps))
    kw = dict(epochs=3, block_size=8)

    # the p = 4 round-synchronous replay of the solver's draws
    ref = serial_replay(ell, loss, epochs=3, block_size=8, seed=0, p=4)
    a0, w0 = np.asarray(ref.alpha), np.asarray(ref.w)

    # 1D: pipeline == oracle, tail rows trained
    a1, w1, g1 = A(sharded_passcode_solve(ell, loss, mesh=mesh1, **kw))
    d1 = max(np.abs(a0 - a1).max(), np.abs(w0 - w1).max())
    assert d1 < 1e-5, d1
    assert np.abs(a1[96:]).sum() > 0  # tail trained, not dropped

    # 2D: pipeline == 1D sequence
    a3, w3, g3 = A(sharded_passcode_solve(ell, loss, mesh=mesh2, **kw))
    d2 = max(np.abs(a1 - a3).max(), np.abs(w1 - w3).max())
    assert d2 < 1e-5, d2
    assert np.abs(g1 - g3).max() < 1e-3 * (1 + np.abs(g1).max()), (g1, g3)

    # fused overlap (delayed): the oracle's sequence too
    kwd = dict(epochs=3, block_size=8, delay_rounds=1, record=False)
    a5, w5, _ = A(sharded_passcode_solve(ell, loss, mesh=mesh2,
                                         use_kernel=True, overlap=True,
                                         **kwd))
    d3 = max(np.abs(a0 - a5).max(), np.abs(w0 - w5).max())
    assert d3 < 1e-5, d3
    print("SUBPROCESS_OK", d1, d2, d3)
""")


def test_multi_device_pipeline_subprocess():
    here = os.path.dirname(os.path.abspath(__file__))
    code = _SUBPROCESS.format(src=os.path.join(here, "..", "src"),
                              tests=here)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
