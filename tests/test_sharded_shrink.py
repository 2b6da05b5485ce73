"""Self-tuning solver (DESIGN.md §12): on-device active-set shrinking,
dynamic repack, and the gap-trend adaptive-asynchrony controller.

Serial semantics: ``sharded_passcode_solve(..., shrink_every=k)`` on a
single device with ``block_size = n`` runs the same update sequence as
the serial reference ``dcd_solve_shrink`` — same PRNG chain, same
mask-recompute schedule, same final unshrunk pass — pinned at atol 1e-5
for hinge and squared-hinge on both delay schedules (at p = 1 the dyn
delayed mode is bit-identical to the synchronous one: a device's own
updates are always visible).  ``shrink_tol = inf`` must reproduce the
plain solve bit-exactly (the mask never freezes anything), including
with repack enabled (the repacked draw over an all-active mask is the
identity reordering).

Multi-device behaviour — the n % p tail staying frozen-safe, repack
actually skipping rounds, and the dyn delayed mode being *genuinely*
stale (others' last-round psum invisible ⇒ different numbers than
synchronous) — runs in an 8-host-device subprocess like the other
sharded test files.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.shrinking import dcd_solve_shrink
from repro.dist.mesh import adaptive_delay_policy, resolve_self_tuning


@pytest.fixture(scope="module")
def tiny_ell(tiny):
    return tiny.X_train


def _assert_close(a, b, tol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0)], ids=["hinge", "sq"],
)
def test_shrink_matches_serial(tiny_dense, loss, delay_rounds):
    """block_size = n, p = 1: the sharded shrink solve is the serial
    ``dcd_solve_shrink`` sequence."""
    n = tiny_dense.shape[0]
    a_ref, w_ref, _, act_ref = dcd_solve_shrink(tiny_dense, loss,
                                                epochs=6, seed=0,
                                                shrink_every=2)
    r = sharded_passcode_solve(tiny_dense, loss, epochs=6, block_size=n,
                               seed=0, shrink_every=2, repack=False,
                               delay_rounds=delay_rounds)
    _assert_close(r.alpha, a_ref)
    _assert_close(r.w_hat, w_ref)
    # the recorded active fraction matches the serial trace
    _assert_close(r.active, act_ref, tol=1e-6)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["unfused", "fused"])
def test_shrink_2d_matches_1d(tiny_ell, use_kernel, hinge):
    """The 2-D feature-sharded engines run the same masked sequence."""
    mesh2 = jax.make_mesh((1, 1), ("data", "model"))
    kw = dict(epochs=4, block_size=32, seed=0, shrink_every=1,
              repack=False)
    r1 = sharded_passcode_solve(tiny_ell, hinge, **kw)
    r2 = sharded_passcode_solve(tiny_ell, hinge, mesh=mesh2,
                                use_kernel=use_kernel, **kw)
    _assert_close(r1.alpha, r2.alpha)
    _assert_close(r1.w_hat, r2.w_hat)


@pytest.mark.parametrize("repack", [False, True], ids=["norepack",
                                                       "repack"])
def test_shrink_tol_inf_bitmatches_plain(tiny_ell, hinge, repack):
    """An infinite tolerance never freezes a coordinate, so the masked
    (and repacked: all-active compaction is the identity) solve is the
    plain pipelined solve bit-for-bit."""
    kw = dict(epochs=3, block_size=32, seed=0)
    r0 = sharded_passcode_solve(tiny_ell, hinge, **kw)
    r1 = sharded_passcode_solve(tiny_ell, hinge, shrink_every=1,
                                shrink_tol=float("inf"), repack=repack,
                                repack_threshold=2.0, **kw)
    assert float(jnp.abs(r0.alpha - r1.alpha).max()) == 0.0
    assert float(jnp.abs(r0.w_hat - r1.w_hat).max()) == 0.0
    assert np.all(np.asarray(r1.active) == 1.0)


def test_logistic_never_shrinks(tiny_ell):
    """Logistic duals are interior — the mask must stay all-active."""
    r = sharded_passcode_solve(tiny_ell, Logistic(C=1.0), epochs=3,
                               block_size=32, shrink_every=1)
    assert np.all(np.asarray(r.active) == 1.0)


def test_wrongly_shrunk_recovery(tiny_dense, hinge):
    """A negative shrink_tol wrongly freezes EVERY coordinate at the
    α = 0 start (hinge projected gradient −1 > tol at the lower bound);
    the final unshrunk pass (LIBLINEAR semantics) must still train the
    model, not return the frozen zeros."""
    r_bad = sharded_passcode_solve(tiny_dense, hinge, epochs=6,
                                   block_size=64, shrink_every=1,
                                   shrink_tol=-2.0, repack=False)
    acts = np.asarray(r_bad.active)
    assert acts.min() == 0.0, acts  # the mask really froze everything
    assert float(jnp.abs(r_bad.alpha).max()) > 0  # recovery pass ran
    # one real (final) epoch: roughly a 1-epoch solve, far below the
    # α = 0 gap
    r_one = sharded_passcode_solve(tiny_dense, hinge, epochs=1,
                                   block_size=64)
    assert float(r_bad.gaps[-1]) <= 2 * float(r_one.gaps[-1]) + 1e-3


def test_eps_metric_recorded(tiny_ell, hinge):
    """The live backward-error ‖w(α) − ŵ‖ rides along with every
    recorded gap and stays at rounding level for the lossless psum."""
    r = sharded_passcode_solve(tiny_ell, hinge, epochs=4, block_size=32,
                               shrink_every=1, gap_every=2)
    eps = np.asarray(r.eps)
    assert eps.shape == np.asarray(r.gaps).shape
    assert np.all(np.isfinite(eps))
    assert eps.max() < 1e-3, eps


def test_controller_monotone_response():
    """Improving gap ⇒ stay async (1); stall/regression ⇒ sync (0);
    monotone: a smaller new gap never lowers the asynchrony."""
    assert int(adaptive_delay_policy(jnp.float32(10.0),
                                     jnp.float32(1.0))) == 1
    assert int(adaptive_delay_policy(jnp.float32(10.0),
                                     jnp.float32(9.8))) == 0
    assert int(adaptive_delay_policy(jnp.float32(10.0),
                                     jnp.float32(12.0))) == 0
    # first record: gap_prev = inf ⇒ always async
    assert int(adaptive_delay_policy(jnp.float32(jnp.inf),
                                     jnp.float32(1e6))) == 1
    gaps = [adaptive_delay_policy(jnp.float32(10.0), jnp.float32(g))
            for g in (0.1, 1.0, 9.0, 9.6, 11.0)]
    vals = [int(g) for g in gaps]
    assert vals == sorted(vals, reverse=True), vals


def test_adaptive_runs_and_records_delay(tiny_ell, hinge):
    """End-to-end adaptive solve: the delay trace is 0/1, starts from
    the delay_rounds seed, and the solve still converges."""
    r = sharded_passcode_solve(tiny_ell, hinge, epochs=8, block_size=32,
                               shrink_every=1, adaptive=True,
                               delay_rounds=1)
    d = np.asarray(r.delay)
    assert set(np.unique(d)) <= {0.0, 1.0}
    assert d[0] == 1.0  # seeded async
    assert float(r.gaps[-1]) < 1.0


def test_adaptive_ratio_anneals_to_sync(tiny_ell, hinge):
    """A strict improvement threshold anneals async→synchronous: the
    policy demands the gap keep halving, so the delay flag must drop
    before the hard-stall default would, and the repack guard (keyed on
    the hard stall, not the annealing threshold) must not be tripped by
    the routine slowdown near the optimum."""
    assert int(adaptive_delay_policy(jnp.float32(10.0), jnp.float32(6.0),
                                     improve_ratio=0.5)) == 0
    assert int(adaptive_delay_policy(jnp.float32(10.0), jnp.float32(4.0),
                                     improve_ratio=0.5)) == 1
    kw = dict(epochs=10, block_size=32, shrink_every=1, adaptive=True,
              delay_rounds=1)
    lax_d = np.asarray(sharded_passcode_solve(
        tiny_ell, hinge, **kw).delay)
    strict = sharded_passcode_solve(tiny_ell, hinge, adaptive_ratio=0.5,
                                    **kw)
    strict_d = np.asarray(strict.delay)
    # the strict controller spends no more async epochs than the lax
    # one and has gone synchronous by the tail; both traces are
    # monotone non-increasing (the back-off is a one-way latch)
    assert strict_d.sum() <= lax_d.sum()
    assert strict_d[-1] == 0.0
    assert np.all(np.diff(strict_d) <= 0), strict_d
    assert np.all(np.diff(lax_d) <= 0), lax_d
    assert float(strict.gaps[-1]) < 1.0


def test_self_tuning_validation(tiny_ell, hinge):
    """Invalid knob combinations raise instead of silently degrading."""
    with pytest.raises(ValueError):  # controller needs the gap signal
        sharded_passcode_solve(tiny_ell, hinge, epochs=1, adaptive=True,
                               record=False)
    with pytest.raises(ValueError):  # repack without a mask to compact
        resolve_self_tuning(0, True, False, overlap_knob="auto",
                            overlap_on=False, record=True)
    with pytest.raises(ValueError):  # overlapped gram vs repacked draw
        mesh2 = jax.make_mesh((1, 1), ("data", "model"))
        sharded_passcode_solve(tiny_ell, hinge, mesh=mesh2, epochs=1,
                               use_kernel=True, overlap=True,
                               delay_rounds=1, shrink_every=1,
                               repack=True)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    ds = make_dataset("tiny")
    full = ds.X_train
    from repro.data.sparse import EllMatrix
    n = 250  # force an n % p tail (250 = 8·31 + 2)
    ell = EllMatrix(full.indices[:n], full.values[:n], full.n_features)
    assert n % 8 != 0  # the padded-tail regime is what we're testing
    loss = Hinge(C=1.0)
    mesh = jax.make_mesh((8,), ("data",))
    kw = dict(mesh=mesh, epochs=8, block_size=8, seed=0)

    # tol=inf (mask never bites, repack never engages at frac = 1.0)
    # == plain, bit-for-bit, with a padded tail
    r0 = sharded_passcode_solve(ell, loss, **kw)
    r1 = sharded_passcode_solve(ell, loss, shrink_every=1,
                                shrink_tol=float("inf"), repack=True,
                                **kw)
    d1 = max(float(jnp.abs(r0.alpha - r1.alpha).max()),
             float(jnp.abs(r0.w_hat - r1.w_hat).max()))
    assert d1 == 0.0, d1
    # forcing repack on (threshold 2.0 > frac) legitimately CHANGES the
    # padded tail's schedule — no-op fill instead of double-updating
    # cycled rows — so expect agreement in quality, not bits
    r1f = sharded_passcode_solve(ell, loss, shrink_every=1,
                                 shrink_tol=float("inf"), repack=True,
                                 repack_threshold=2.0, **kw)
    assert float(r1f.gaps[-1]) < 2 * float(r0.gaps[-1]) + 1e-2

    # real shrinking converges, active fraction decreases, tail trained
    rs = sharded_passcode_solve(ell, loss, shrink_every=1, repack=False,
                                **kw)
    acts = np.asarray(rs.active)
    assert acts[-1] <= acts[1] < 1.0, acts
    assert float(rs.gaps[-1]) < 2 * float(r0.gaps[-1]) + 1e-2
    assert np.abs(np.asarray(rs.alpha)[-(n % 8):]).sum() > 0

    # dyn delayed mode is REAL staleness at p > 1: different numbers
    # than synchronous, still convergent inside the τ bound (B = 4:
    # delayed τ ≈ 2·4·7 = 56 ≪ n)
    kw4 = dict(kw, block_size=4)
    rs4 = sharded_passcode_solve(ell, loss, shrink_every=1, repack=False,
                                 **kw4)
    rd = sharded_passcode_solve(ell, loss, shrink_every=1, repack=False,
                                delay_rounds=1, **kw4)
    d2 = float(jnp.abs(rs4.w_hat - rd.w_hat).max())
    assert d2 > 1e-6, d2
    # doubled τ costs roughly one epoch of progress, no more
    assert float(rd.gaps[-1]) < 4 * float(rs4.gaps[-1]) + 1e-2

    # this toy at p = 8, B = 8 sits near the Liu–Wright boundary:
    # repacked epochs (τ × 1/frac) genuinely DIVERGE mid-solve — and
    # the adaptive controller's sticky repack guard catches exactly
    # that, recovering a convergent end state
    rr = sharded_passcode_solve(ell, loss, shrink_every=1, repack=True,
                                **kw)
    g_rr = np.asarray(rr.gaps)[1:-1]
    # the gap falls, then RISES again once repack engages — real
    # divergence, recovered only by the final unshrunk pass
    post_min = g_rr[np.argmin(g_rr):]
    assert post_min.max() > post_min[0], g_rr
    ra = sharded_passcode_solve(ell, loss, shrink_every=1, repack=True,
                                adaptive=True, **kw)
    # the sticky repack guard ends the adaptive run below the peak the
    # repack-only run climbs to after its minimum
    assert float(ra.gaps[-1]) < post_min.max(), (
        float(ra.gaps[-1]), g_rr)
    dtr = np.asarray(ra.delay)
    # seeded synchronous: the one-way latch never raises asynchrony,
    # so the intervention here is the sticky repack guard (rpok)
    # tripping on the hard stall — evidenced by the convergent end
    # state the repack-only run above cannot reach
    assert dtr.max() == 0.0, dtr
    assert float(ra.gaps[-1]) < 5.0, float(ra.gaps[-1])
    print("SUBPROCESS_OK", d1, d2)
""")


def test_multi_device_shrink_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
