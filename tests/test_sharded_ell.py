"""Sparse (ELL) fast path of the sharded PASSCoDe solver — the three
engines that can consume an ``EllMatrix`` (unfused jnp ELL, fused Pallas
ELL in interpret mode, and the dense reference) must agree to atol 1e-5
for every loss in the family and for delayed (stale-τ) rounds, the tail
rows of a non-p-divisible n must be trained rather than dropped, and
``dense_to_ell``/``to_dense`` must round-trip on ragged-row matrices.
The fused kernel streams each row from HBM: one block of it matches the
jnp engine's block at a width that is not a multiple of 128, on a block
that revisits rows, and with the active-mask and label operands.

Multi-device agreement (including the masked tail padding) is covered by
an 8-host-device subprocess, same pattern as tests/test_sharded_kernel.py.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import lane_edge_rows
from repro.core import duality_gap, sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.sharded import _local_block_update_ell, _resolve_kernel_mode
from repro.data.sparse import EllMatrix, dense_to_ell
from repro.dist.mesh import (
    dcd_ell_kernel_fits,
    dcd_ell_kernel_vmem_bytes,
    dcd_kernel_fits,
    lane_pad,
)
from repro.kernels.dcd_ell import stream_rows
from repro.kernels.ops import dcd_ell_block_update_pallas

LOSSES = [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)]
LOSS_IDS = ["hinge", "sq", "logistic"]


@pytest.fixture(scope="module")
def tiny_ell(tiny):
    return tiny.X_train


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_ell_engine_equivalence(tiny_ell, tiny_dense, loss, delay_rounds):
    """dense jnp == ELL jnp == ELL Pallas, same blocks, atol 1e-5."""
    kw = dict(epochs=2, block_size=32, delay_rounds=delay_rounds,
              record=False)
    r_dense = sharded_passcode_solve(tiny_dense, loss, **kw)
    r_ell = sharded_passcode_solve(tiny_ell, loss, **kw)
    r_fused = sharded_passcode_solve(tiny_ell, loss, use_kernel=True, **kw)
    for r in (r_ell, r_fused):
        np.testing.assert_allclose(np.asarray(r.alpha),
                                   np.asarray(r_dense.alpha),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(r.w_hat),
                                   np.asarray(r_dense.w_hat),
                                   rtol=1e-5, atol=1e-5)
        # dummy slot + lane padding sliced off the returned primal
        assert r.w_hat.shape == r_dense.w_hat.shape


def test_ell_converges(tiny_ell, hinge):
    r = sharded_passcode_solve(tiny_ell, hinge, epochs=12, block_size=32)
    assert float(r.gaps[-1]) < 0.5


def test_ell_auto_mode_falls_back_on_cpu(tiny_ell, hinge):
    use_k, interpret = _resolve_kernel_mode("auto", 128, 80, ell=True)
    assert use_k is False and interpret is True
    r = sharded_passcode_solve(tiny_ell, hinge, epochs=3, block_size=32,
                               use_kernel="auto", record=False)
    assert r.w_hat.shape[0] == tiny_ell.n_features


def test_ell_vmem_policy_admits_what_dense_rejects():
    """The reason the sparse path exists: paper-scale d blows the dense
    n_loc·d̃ VMEM budget, while the streamed ELL kernel keeps only the
    padded primal resident — so both paper shards are admitted at any
    row count (rcv1 d≈47k in 0.5 MB, news20 d≈1.36M in 11 MB), and a
    primal over budget (webspam d≈16.6M) is still rejected."""
    for n_loc, d in ((677_399, 47_236), (16_000, 1_355_191)):
        assert not dcd_kernel_fits(n_loc, d)
        assert dcd_ell_kernel_fits(d)
    assert dcd_ell_kernel_vmem_bytes(47_236) < 2**20
    assert not dcd_kernel_fits(8, 1_355_191)
    assert not dcd_ell_kernel_fits(16_609_143)


def _stream_case(case: str, seed: int = 7):
    """One block's operands for the streamed kernel and the jnp engine:
    rows of 73 nonzeros over d = 300 (k and d₁ off the 128-lane tile),
    a padding row, and a primal and duals away from zero; or, for
    ``lane_edges``, ``lane_edge_rows`` padded to the widest row with the
    dummy slot, each row visited once."""
    rng = np.random.default_rng(seed)
    if case == "lane_edges":
        d = 1023
        rows, rvals, w = lane_edge_rows(d)
        k = max(len(r) for r in rows)
        n = len(rows) + 1  # and an all-padding row
        ids = np.full((n, k), d, np.int32)
        vals = np.zeros((n, k), np.float32)
        for i, (r, v) in enumerate(zip(rows, rvals)):
            ids[i, :len(r)], vals[i, :len(v)] = r, v
        sq = np.maximum((vals * vals).sum(1), 1.0).astype(np.float32)
        alpha = rng.uniform(0.0, 1.0, n).astype(np.float32)
        return (jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(sq),
                jnp.asarray(alpha), jnp.asarray(w),
                jnp.asarray(rng.permutation(n).astype(np.int32)), None, None)
    n, k, d, b = (40, 73, 300, 64) if case == "revisit" else (150, 73, 300, 64)
    ids = np.stack([np.sort(rng.choice(d, k, replace=False))
                    for _ in range(n)]).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    ids[-1], vals[-1] = d, 0.0  # an all-padding row, as the tail pads
    sq = np.maximum((vals * vals).sum(1), 1e-12).astype(np.float32)
    sq[-1] = 1.0
    if case == "revisit":
        # n_loc < B: a whole pass, then the prefix again (the tail block),
        # and a few rows twice in a row
        idx = np.concatenate([rng.permutation(n), np.arange(b - n - 4),
                              [3, 3, 17, 17]])
    else:
        idx = rng.permutation(n)[:b]
    w = np.zeros(lane_pad(d + 1), np.float32)
    w[:d] = 0.2 * rng.standard_normal(d)
    alpha = rng.uniform(0.0, 1.0, n).astype(np.float32)
    act = y = None
    if case == "act_y":
        act = jnp.asarray(rng.random(n) > 0.3)
        y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    return (jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(sq),
            jnp.asarray(alpha), jnp.asarray(w),
            jnp.asarray(idx.astype(np.int32)), act, y)


@pytest.mark.parametrize("case", ["k73", "revisit", "act_y", "lane_edges"])
@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_streamed_block_matches_jnp_engine(loss, case):
    """One block of the streamed kernel (interpret mode) against the jnp
    ELL engine on the same operands: same α and Δw to atol 1e-5, and to
    the bit on the exact ``lane_edges`` rows."""
    cols, vals, sq, alpha, w, idx, act, y = _stream_case(case)
    a_ref, dw_ref = _local_block_update_ell(cols, vals, sq, alpha, w, idx,
                                            loss, act=act, y=y)
    a_k, dw_k = dcd_ell_block_update_pallas(
        stream_rows(cols, vals), sq, alpha, w, idx, k=cols.shape[1],
        loss=loss, interpret=True, active=act, y=y)
    if case == "lane_edges":
        np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_ref))
        np.testing.assert_array_equal(np.asarray(dw_k), np.asarray(dw_ref))
        assert np.asarray(dw_k)[:-1].any()
    else:
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_ref),
                                   rtol=1e-5, atol=1e-5)
    d = w.shape[0] - 1 if case == "lane_edges" else 300
    assert np.all(np.asarray(dw_k)[d:] == 0.0)  # dummy and lane slots


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_streamed_engine_small_shard(loss, delay_rounds):
    """A shard smaller than one block (n_loc = 40 < B = 64): every round
    revisits rows, and the solve agrees with the jnp engine."""
    cols, vals, *_ = _stream_case("revisit")
    X = EllMatrix(cols, vals, 300)
    kw = dict(epochs=2, block_size=64, delay_rounds=delay_rounds,
              record=False)
    r_ell = sharded_passcode_solve(X, loss, **kw)
    r_fused = sharded_passcode_solve(X, loss, use_kernel=True, **kw)
    assert r_fused.engine == "ell/pallas-stream-interpret"
    np.testing.assert_allclose(np.asarray(r_fused.alpha),
                               np.asarray(r_ell.alpha), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_fused.w_hat),
                               np.asarray(r_ell.w_hat), rtol=1e-5, atol=1e-5)


def test_gap_every_subsamples_and_matches(tiny_ell, hinge):
    r2 = sharded_passcode_solve(tiny_ell, hinge, epochs=5, block_size=32,
                                gap_every=2)
    r1 = sharded_passcode_solve(tiny_ell, hinge, epochs=5, block_size=32)
    # epochs 2, 4 and the final 5 → 3 recorded gaps
    assert r2.gaps.shape == (3,)
    assert r1.gaps.shape == (5,)
    assert float(r2.gaps[-1]) == pytest.approx(float(r1.gaps[-1]), rel=1e-6)
    assert float(r2.gaps[0]) == pytest.approx(float(r1.gaps[1]), rel=1e-6)


def test_tail_rows_trained_not_dropped(tiny_dense, hinge):
    """Non-divisible n on a 1-device mesh exercises the ceil/n_pad path;
    every row (including the old dropped tail) must receive updates."""
    X = np.asarray(tiny_dense)[:101]
    r = sharded_passcode_solve(X, hinge, epochs=3, block_size=16,
                               record=False)
    assert r.alpha.shape == (101,)
    assert float(jnp.sum(jnp.abs(r.alpha))) > 0
    g = float(duality_gap(r.alpha, jnp.asarray(X), hinge))
    assert np.isfinite(g)


# ------------------------------------------------ ELL round-trip ----


@st.composite
def ragged_matrix(draw):
    """Small dense matrix with wildly ragged per-row sparsity."""
    n = draw(st.integers(min_value=1, max_value=12))
    d = draw(st.integers(min_value=1, max_value=24))
    rng = np.random.default_rng(draw(st.integers(min_value=0,
                                                 max_value=2**31 - 1)))
    dense = rng.standard_normal((n, d)).astype(np.float32)
    # per-row keep probability in [0, 1] → rows from empty to full
    keep = rng.random((n, 1)) * rng.random((n, d))
    return np.where(keep > 0.5, dense, 0.0).astype(np.float32)


@given(dense=ragged_matrix())
@settings(max_examples=30, deadline=None)
def test_dense_to_ell_round_trip(dense):
    ell = dense_to_ell(dense)
    assert ell.k_max >= 1
    assert int(ell.indices.max()) <= dense.shape[1]  # padding id == d
    back = np.asarray(ell.to_dense())
    np.testing.assert_array_equal(back, dense)
    # row norms survive the layout change exactly
    np.testing.assert_allclose(np.asarray(ell.row_sq_norms()),
                               (dense * dense).sum(axis=1), rtol=1e-6)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data.sparse import dense_to_ell
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    # 100 % 8 != 0: the masked tail padding is on the hot path here
    X = np.asarray(make_dataset("tiny").dense_train())[:100]
    ell = dense_to_ell(X)
    loss = Hinge(C=1.0)
    mesh = jax.make_mesh((8,), ("data",))
    kw = dict(mesh=mesh, epochs=3, block_size=8, record=False)
    r0 = sharded_passcode_solve(X, loss, **kw)
    r1 = sharded_passcode_solve(ell, loss, **kw)
    r2 = sharded_passcode_solve(ell, loss, use_kernel=True, **kw)
    assert r0.alpha.shape == (100,)
    assert float(jnp.sum(jnp.abs(r0.alpha[96:]))) > 0  # tail trained
    d1 = float(jnp.max(jnp.abs(r0.alpha - r1.alpha)))
    d2 = float(jnp.max(jnp.abs(r0.w_hat - r1.w_hat)))
    d3 = float(jnp.max(jnp.abs(r1.alpha - r2.alpha)))
    d4 = float(jnp.max(jnp.abs(r1.w_hat - r2.w_hat)))
    assert max(d1, d2, d3, d4) < 1e-5, (d1, d2, d3, d4)
    print("SUBPROCESS_OK", d1, d2, d3, d4)
""")


def test_multi_device_ell_equivalence_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
