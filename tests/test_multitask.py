"""Multi-task one-vs-rest solver (DESIGN.md §16): K binary problems
sharing one X, solved as a single pipelined dispatch with a leading
(K,) task axis.

The equivalence spine has two rungs:

  * K = 1 must be BIT-identical to the binary path
    (``np.testing.assert_array_equal``) — the vmapped task closure runs
    the same update sequence, and folding ±1 labels on read is an IEEE
    sign flip, exact against the binary path's pre-folded rows;
  * K > 1 must match the loop-over-K binary reference at atol 1e-5 per
    class for every loss — the acceptance bar for the one-dispatch
    claim.

Plus: ``ovr_labels``/``ovr_decode`` round-trip (property test),
``predict_multiclass`` units, segmented checkpoint/resume with the task
axis intact, the task-sharded mesh in an 8-device subprocess, VMEM
policy with the ``n_tasks`` factor, and the multiclass serve engine +
incremental trainer.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    multiclass_accuracy,
    predict_multiclass,
    sharded_passcode_solve,
)
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.data import MultitaskLabels, multitask_labels, ovr_decode, ovr_labels
from repro.data.sparse import dense_to_ell
from repro.dist import task_axis_policy
from repro.dist.mesh import (
    dcd_feature_kernel_vmem_bytes,
    dcd_kernel_vmem_bytes,
)
from repro.resilience import solve_segmented


def _data(n=96, d=20, n_classes=4, seed=0):
    """Unfolded dense rows + integer class ids with a planted signal."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    X[np.arange(n), y % d] += 2.0
    return jnp.asarray(X), y


def _bit_eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ===================================================== K=1 bit parity ====


K1_VARIANTS = {
    "dense": dict(),
    "ell": dict(),
    "delay": dict(delay_rounds=1),
    "shrink": dict(shrink_every=1),
    "adaptive": dict(adaptive=True, delay_rounds=1),
    "fused_ell": dict(use_kernel=True),
}


@pytest.mark.parametrize("variant", sorted(K1_VARIANTS), ids=str)
def test_k1_bit_identical_1d(variant):
    """A (1, n) label matrix reproduces the binary solve bit-for-bit on
    the 1-D path: pre-folded rows vs fold-on-read are the same IEEE
    sign flips, and the vmapped closure runs the same update order.
    The solver state (α, w) is bit-equal; the recorded duality gap is
    only reduction-order equal (its docstring's documented caveat —
    XLA lowers the batched K=1 row-matvec with a different accumulation
    order than the unbatched one)."""
    X, y_int = _data(n=64, d=16, n_classes=2)
    y = np.where(np.asarray(y_int) == 0, 1.0, -1.0).astype(np.float32)
    kw = dict(epochs=2, block_size=16, **K1_VARIANTS[variant])
    if variant in ("dense",):
        Xb, Xm = X * y[:, None], X
    else:
        Xb, Xm = dense_to_ell(X * y[:, None]), dense_to_ell(X)
    ref = sharded_passcode_solve(Xb, Hinge(C=1.0), **kw)
    r = sharded_passcode_solve(Xm, Hinge(C=1.0), y=y[None], **kw)
    assert np.asarray(r.alpha).shape == (1, X.shape[0])
    _bit_eq(r.alpha[0], ref.alpha)
    _bit_eq(r.w_hat[0], ref.w_hat)
    np.testing.assert_allclose(np.asarray(r.gaps)[0],
                               np.asarray(ref.gaps), rtol=1e-6)


def test_k1_bit_identical_2d():
    """Same bit parity on the 2-D feature-sharded engine."""
    X, y_int = _data(n=64, d=16, n_classes=2)
    y = np.where(np.asarray(y_int) == 0, 1.0, -1.0).astype(np.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    kw = dict(mesh=mesh, epochs=2, block_size=16)
    ref = sharded_passcode_solve(dense_to_ell(X * y[:, None]),
                                 Hinge(C=1.0), **kw)
    r = sharded_passcode_solve(dense_to_ell(X), Hinge(C=1.0),
                               y=y[None], **kw)
    _bit_eq(r.alpha[0], ref.alpha)
    _bit_eq(r.w_hat[0], ref.w_hat)
    _bit_eq(r.gaps[0], ref.gaps)


# ================================================ K>1 vs loop-over-K ====


@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)],
    ids=["hinge", "sq", "logistic"],
)
def test_k16_one_dispatch_matches_loop(loss):
    """The acceptance bar: a K=16 OvR solve runs as ONE pipelined
    dispatch and agrees with the loop-over-K binary reference at atol
    1e-5 per class."""
    K = 16
    X, y_int = _data(n=96, d=20, n_classes=K, seed=1)
    Y = ovr_labels(y_int, K)
    kw = dict(epochs=3, block_size=16)
    r = sharded_passcode_solve(X, loss, y=Y, **kw)
    assert np.asarray(r.alpha).shape == (K, X.shape[0])
    assert np.asarray(r.w_hat).shape == (K, X.shape[1])
    for k in range(K):
        ref = sharded_passcode_solve(X * np.asarray(Y)[k][:, None],
                                     loss, **kw)
        np.testing.assert_allclose(np.asarray(r.alpha)[k],
                                   np.asarray(ref.alpha),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(r.w_hat)[k],
                                   np.asarray(ref.w_hat),
                                   rtol=1e-5, atol=1e-5)


def test_multitask_ell_shrink_matches_loop():
    """Sparse path + per-task shrink masks: each class keeps its own
    active set, still matching independent binary solves."""
    K = 3
    X, y_int = _data(n=96, d=20, n_classes=K, seed=2)
    Y = np.asarray(ovr_labels(y_int, K))
    kw = dict(epochs=3, block_size=16, shrink_every=1)
    r = sharded_passcode_solve(dense_to_ell(np.asarray(X)),
                               Hinge(C=1.0), y=Y, **kw)
    for k in range(K):
        ref = sharded_passcode_solve(
            dense_to_ell(np.asarray(X) * Y[k][:, None]),
            Hinge(C=1.0), **kw)
        np.testing.assert_allclose(np.asarray(r.alpha)[k],
                                   np.asarray(ref.alpha),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(r.w_hat)[k],
                                   np.asarray(ref.w_hat),
                                   rtol=1e-5, atol=1e-5)


# =============================================== input validation ========


def test_multitask_label_validation():
    X, y_int = _data(n=32, d=8, n_classes=2)
    loss = Hinge(C=1.0)
    bad = np.asarray(ovr_labels(y_int, 2)).copy()
    bad[0, 0] = 0.5
    with pytest.raises(ValueError):
        sharded_passcode_solve(X, loss, y=bad, epochs=1)
    with pytest.raises(ValueError):  # column count != n
        sharded_passcode_solve(X, loss, y=np.ones((2, 31), np.float32),
                               epochs=1)


def test_task_axis_policy_validation():
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError):
        task_axis_policy(0, mesh=mesh)
    pod_task = jax.make_mesh((1, 1, 1), ("task", "pod", "data"))
    with pytest.raises(ValueError):
        task_axis_policy(4, mesh=pod_task)
    assert task_axis_policy(4, mesh=mesh) == 4


# ====================================================== labels API ======


@given(ids=st.lists(st.integers(0, 9), min_size=1, max_size=64))
@settings(max_examples=30, deadline=None)
def test_ovr_roundtrip(ids):
    """ovr_decode ∘ ovr_labels is the identity on class ids."""
    K = max(ids) + 1
    Y = ovr_labels(np.asarray(ids), K)
    assert Y.shape == (K, len(ids))
    cols = np.asarray(Y)
    assert np.all(np.abs(cols) == 1.0)
    assert np.all((cols == 1.0).sum(axis=0) == 1)
    np.testing.assert_array_equal(np.asarray(ovr_decode(Y)),
                                  np.asarray(ids, np.int32))


def test_ovr_labels_validation():
    with pytest.raises(ValueError):
        ovr_labels(np.asarray([0, 3]), 3)  # id out of range
    with pytest.raises(ValueError):
        ovr_labels(np.asarray([-1, 0]), 2)
    with pytest.raises(ValueError):
        ovr_labels(np.asarray([0.5, 1.0]))  # non-integral
    with pytest.raises(ValueError):
        ovr_labels(np.zeros((2, 2), np.int32))  # not 1-D
    with pytest.raises(ValueError):
        ovr_labels(np.asarray([], np.int32))
    mt = multitask_labels([0, 1, 2, 1])
    assert isinstance(mt, MultitaskLabels)
    assert mt.n_classes == 3 and mt.n_rows == 4


def test_predict_multiclass_units():
    W = np.asarray([[1.0, 0.0], [0.0, 1.0], [-1.0, -1.0]], np.float32)
    X = np.asarray([[2.0, 0.1], [0.1, 2.0], [-3.0, -3.0]], np.float32)
    pred = np.asarray(predict_multiclass(W, X))
    np.testing.assert_array_equal(pred, [0, 1, 2])
    assert float(multiclass_accuracy(W, X, [0, 1, 2])) == 1.0
    assert float(multiclass_accuracy(W, X, [0, 1, 0])) == pytest.approx(
        2.0 / 3.0)
    with pytest.raises(ValueError):
        predict_multiclass(W[0], X)  # needs a (K, d) stack


# =========================================== segmented checkpointing ====


def test_segmented_multitask_resume_bit_identical(tmp_path):
    """Checkpoint/resume round-trips the task axis: the resumed K-class
    solve lands on the uninterrupted run's exact (K, n)/(K, d) state."""
    import shutil

    K = 16
    X, y_int = _data(n=64, d=16, n_classes=K, seed=3)
    Y = np.asarray(ovr_labels(y_int, K))
    d = str(tmp_path)
    kw = dict(epochs=6, checkpoint_every=2, seed=3, ckpt_dir=d, keep=10,
              y=Y)
    full = solve_segmented(X, Hinge(C=0.5), **kw)
    assert np.asarray(full.result.alpha).shape == (K, X.shape[0])
    for s in (4, 6):
        shutil.rmtree(os.path.join(d, f"ckpt_{s}"))
    res = solve_segmented(X, Hinge(C=0.5), resume=True, **kw)
    assert res.resumed_from == 2
    _bit_eq(full.result.alpha, res.result.alpha)
    _bit_eq(full.result.w_hat, res.result.w_hat)
    _bit_eq(full.result.gaps, res.result.gaps)


# ======================================================= VMEM policy ====


def test_vmem_n_tasks_factor():
    """n_tasks=1 reproduces the binary formula exactly; per-task state
    grows the working set monotonically while shared X terms do not
    re-count.  (The streamed ELL kernel has no such factor: it runs one
    head's primal at a time over its task grid.)"""
    for fn, args in ((dcd_kernel_vmem_bytes, (512, 64)),
                     (dcd_feature_kernel_vmem_bytes, (512, 8, 64))):
        base = fn(*args)
        assert fn(*args, n_tasks=1) == base
        prev = base
        for k in (2, 4, 8):
            cur = fn(*args, n_tasks=k)
            assert cur > prev
            prev = cur
        # per-task growth is strictly less than replicating everything
        assert fn(*args, n_tasks=8) < 8 * base


# ===================================================== serve layer ======


def _ell_rows(rng, n, d, k):
    from repro.data.sparse import EllMatrix

    idx = np.stack([rng.choice(d, size=k, replace=False)
                    for _ in range(n)]).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    return EllMatrix(idx, val, d)


def test_serve_multiclass_end_to_end():
    """(K, d) snapshot stack → one dispatch scores all heads; the
    outcome carries argmax label + per-head margins; the incremental
    trainer warm-starts the (K, n) dual carry across an append."""
    from repro.serve import (
        IncrementalTrainer,
        ScoreOutcome,
        ServeEngine,
        SnapshotStore,
        snapshot_from_result,
    )

    rng = np.random.default_rng(0)
    K, n, d, kmax = 4, 64, 16, 5
    X0 = _ell_rows(rng, n, d, kmax)
    W_true = rng.normal(size=(K, d)).astype(np.float32)
    wp = np.zeros((K, d + 1), np.float32)
    wp[:, :d] = W_true
    y0 = (wp[:, np.asarray(X0.indices)]
          * np.asarray(X0.values)[None]).sum(-1).argmax(0).astype(np.int32)

    tr = IncrementalTrainer(X0, SquaredHinge(C=1.0), n_classes=K, y0=y0,
                            epochs=5)
    res = tr.fit()
    assert res is not None
    assert tr.alpha.shape == (K, n) and tr.w.shape == (K, d)

    snap = snapshot_from_result(res, 1)
    assert snap.w_pad.shape == (K, d + 1) and snap.n_classes == K
    eng = ServeEngine(SnapshotStore(snap), k_max=kmax, trainer=tr)
    tickets = [eng.submit(cols=np.asarray(X0.indices)[i],
                          vals=np.asarray(X0.values)[i])
               for i in range(8)]
    eng.step()
    for i, t in enumerate(tickets):
        out = t.result(5.0)
        assert isinstance(out, ScoreOutcome)
        assert len(out.margins) == K
        assert out.label == int(np.argmax(out.margins))
        ref = (tr.w[:, np.asarray(X0.indices)[i]]
               * np.asarray(X0.values)[i]).sum(-1)
        np.testing.assert_allclose(np.asarray(out.margins), ref,
                                   rtol=1e-5, atol=1e-5)

    # streaming append: ids buffer raw, α re-enters as a (K, n) carry
    Xn = _ell_rows(rng, 24, d, kmax)
    yn = (wp[:, np.asarray(Xn.indices)]
          * np.asarray(Xn.values)[None]).sum(-1).argmax(0).astype(np.int32)
    tr.add_labeled(Xn, yn)
    res2 = tr.resolve()
    assert res2 is not None
    assert tr.alpha.shape == (K, n + 24) and tr.w.shape == (K, d)
    eng.publish(snapshot_from_result(res2, 2))
    t = eng.submit(cols=np.asarray(Xn.indices)[0],
                   vals=np.asarray(Xn.values)[0])
    eng.step()
    assert t.result(5.0).version == 2
    eng.stop()


def test_serve_binary_outcome_unchanged():
    """Binary snapshots keep the old outcome shape: label −1, empty
    margins, scalar score."""
    from repro.serve import ServeEngine, SnapshotStore, make_snapshot

    w = np.arange(6, dtype=np.float32)
    snap = make_snapshot(w, 1)
    assert snap.w_pad.shape == (7,) and snap.n_classes == 0
    eng = ServeEngine(SnapshotStore(snap), k_max=3)
    t = eng.submit(cols=[1, 4], vals=[2.0, 0.5])
    eng.step()
    out = t.result(5.0)
    assert out.label == -1 and out.margins == ()
    assert out.score == pytest.approx(1.0 * 2.0 + 4.0 * 0.5)
    eng.stop()


def test_trainer_multiclass_validation():
    from repro.serve import IncrementalTrainer

    rng = np.random.default_rng(1)
    X0 = _ell_rows(rng, 16, 8, 3)
    with pytest.raises(ValueError):  # ids required for multiclass
        IncrementalTrainer(X0, Hinge(C=1.0), n_classes=3)
    with pytest.raises(ValueError):  # K=1 is not a multiclass problem
        IncrementalTrainer(X0, Hinge(C=1.0), n_classes=1,
                           y0=np.zeros(16, np.int32))
    with pytest.raises(ValueError):  # ids out of range
        IncrementalTrainer(X0, Hinge(C=1.0), n_classes=3,
                           y0=np.full(16, 3, np.int32))
    with pytest.raises(ValueError):  # y0 meaningless for binary
        IncrementalTrainer(X0, Hinge(C=1.0), y0=np.zeros(16, np.int32))
    tr = IncrementalTrainer(X0, Hinge(C=1.0), n_classes=3,
                            y0=np.zeros(16, np.int32))
    with pytest.raises(ValueError):  # pending ids out of range
        tr.add_labeled(_ell_rows(rng, 4, 8, 3),
                       np.asarray([0, 1, 2, 3], np.int32))


# ================================================ multi-device mesh =====


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data import ovr_labels
    from repro.dist import solver_mesh, solver_mesh_tasks, task_axis_policy

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(0)
    K, n, d = 4, 100, 16   # 100 % 4 != 0: masked row tail stays hot
    X = rng.normal(size=(n, d)).astype(np.float32)
    y_int = rng.integers(0, K, size=n)
    Y = np.asarray(ovr_labels(y_int, K))
    loss = Hinge(C=1.0)
    kw = dict(epochs=3, block_size=8)

    # the task-sharded mesh splits the (K,) axis over 2 devices and the
    # rows over 4 — block draws depend on the data-axis size, so the
    # reference runs on a matched-p plain mesh
    mesh_t = solver_mesh_tasks(task=2, data=4)
    mesh_p = solver_mesh("data", n_devices=4)
    task_axis_policy(K, mesh=mesh_t)
    try:
        task_axis_policy(3, mesh=mesh_t)   # 3 % 2 != 0
        raise SystemExit("uneven K admitted")
    except ValueError:
        pass

    r_t = sharded_passcode_solve(X, loss, y=Y, mesh=mesh_t, **kw)
    r_p = sharded_passcode_solve(X, loss, y=Y, mesh=mesh_p, **kw)
    d1 = max(np.abs(np.asarray(r_t.alpha) - np.asarray(r_p.alpha)).max(),
             np.abs(np.asarray(r_t.w_hat) - np.asarray(r_p.w_hat)).max())
    assert d1 < 1e-5, d1

    # and the plain-mesh multitask run matches loop-over-K binary
    d2 = 0.0
    for k in range(K):
        ref = sharded_passcode_solve(X * Y[k][:, None], loss,
                                     mesh=mesh_p, **kw)
        d2 = max(d2,
                 np.abs(np.asarray(r_p.alpha)[k]
                        - np.asarray(ref.alpha)).max(),
                 np.abs(np.asarray(r_p.w_hat)[k]
                        - np.asarray(ref.w_hat)).max())
    assert d2 < 1e-5, d2
    print("SUBPROCESS_OK", d1, d2)
""")


def test_task_mesh_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
