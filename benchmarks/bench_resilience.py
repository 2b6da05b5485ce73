"""Resilient solver benchmark (DESIGN.md §14): what fault tolerance
costs when nothing fails, and what a fault costs when it does.

Section 1 (overhead): the same solve dispatched whole
(``sharded_passcode_solve``), segmented (watchdog on, no persistence),
and segmented-with-checkpointing — the segmentation + watchdog tax and
the per-segment checkpoint cost, plus the raw ``save_checkpoint`` wall
time for the solver state (the I/O floor the segment cadence should be
chosen against).

Section 2 (recovery): one run per armed fault class (NaN-poisoned
psum, corrupted payload, dropped cross-pod merge) against its
fault-free twin: recovery wall-clock ratio, rollbacks taken, and the
epochs-lost-per-fault the rollback recomputed.  Every recovery is also
checked bit-equal to the clean run — a row that says ``recovered=False``
is a regression, not a perf number.

``main()`` returns rows for benchmarks/run.py to persist as
BENCH_resilience.json; ``--smoke`` shrinks everything to a CI-budget
sanity pass.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

import numpy as np

import jax

from benchmarks.common import emit, timeit
from repro.core.duals import Hinge
from repro.core.sharded import sharded_passcode_solve
from repro.dist.mesh import make_mesh
from repro.resilience import FaultPlan, load_solver_state, solve_segmented
from repro.train.checkpoint import latest_step, save_checkpoint


def _make_dense(rng, n, d):
    X = rng.standard_normal((n, d)).astype(np.float32)
    X /= np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1.0)
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    return X * y[:, None]


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out.result if hasattr(out, "result") else out)
    return out, time.perf_counter() - t0


def _bench_overhead(rows, *, smoke: bool):
    n, d = (128, 32) if smoke else (512, 128)
    epochs, seg = (4, 2) if smoke else (12, 3)
    nseg = epochs // seg
    loss = Hinge(C=1.0)
    X = _make_dense(np.random.default_rng(7), n, d)
    kw = dict(epochs=epochs, seed=0, block_size=32)
    whole, t_whole = _timed(lambda: sharded_passcode_solve(X, loss, **kw))
    r_seg, t_seg = _timed(lambda: solve_segmented(
        X, loss, checkpoint_every=seg, **kw))
    ck = tempfile.mkdtemp(prefix="bench_resil_")
    try:
        r_ck, t_ck = _timed(lambda: solve_segmented(
            X, loss, checkpoint_every=seg, ckpt_dir=ck, **kw))
        # per-segment checkpoint cost, measured directly on the real
        # payload (the end-to-end delta drowns in compile noise at this
        # scale): re-save the exact state dict the last boundary wrote
        state = load_solver_state(ck, latest_step(ck))
        t_save = timeit(lambda: save_checkpoint(ck, 999, state),
                        warmup=1, iters=3)
    finally:
        shutil.rmtree(ck, ignore_errors=True)
    ok = bool(np.array_equal(np.asarray(whole.w_hat),
                             np.asarray(r_seg.result.w_hat)))
    rows.append({
        "name": f"resilience/overhead/segmented/n={n},d={d}",
        "us_per_call": t_seg * 1e6,
        "derived": (f"epochs={epochs},segments={nseg},"
                    f"vs_whole={t_seg / t_whole:.3f}x,bit_match={ok}"),
    })
    rows.append({
        "name": f"resilience/overhead/checkpointed/n={n},d={d}",
        "us_per_call": t_ck * 1e6,
        "derived": (f"segments={nseg},"
                    f"ckpt_us_per_segment={t_save * 1e6:.1f},"
                    f"vs_segmented={t_ck / t_seg:.3f}x"),
    })


def _bench_recovery(rows, *, smoke: bool):
    n, d = (128, 32) if smoke else (512, 128)
    epochs, seg = (4, 2) if smoke else (12, 3)
    loss = Hinge(C=1.0)
    X = _make_dense(np.random.default_rng(11), n, d)
    mid = epochs // 2  # fault epoch: mid-solve, second segment
    pod_mesh = make_mesh((1, len(jax.devices())), ("pod", "data"))
    cases = [
        ("nan_psum", FaultPlan(nan_psum_epoch=mid),
         dict(delay_rounds=1)),
        ("payload", FaultPlan(corrupt_payload_segment=1,
                              corrupt_frac=0.2), dict()),
        ("drop_merge", FaultPlan(drop_merge_epoch=mid),
         dict(mesh=pod_mesh)),
    ]
    for name, plan, extra in cases:
        kw = dict(epochs=epochs, checkpoint_every=seg, seed=0,
                  block_size=32, **extra)
        clean, t_clean = _timed(lambda: solve_segmented(X, loss, **kw))
        r, t_fault = _timed(lambda: solve_segmented(
            X, loss, fault_plan=plan, **kw))
        ok = bool(np.array_equal(np.asarray(clean.result.w_hat),
                                 np.asarray(r.result.w_hat)))
        rows.append({
            "name": f"resilience/recovery/{name}/n={n},d={d}",
            "us_per_call": t_fault * 1e6,
            "derived": (f"vs_clean={t_fault / t_clean:.3f}x,"
                        f"rollbacks={r.rollbacks},"
                        f"epochs_lost={r.epochs_lost},"
                        f"rung={r.rung},recovered={ok}"),
        })


def main(smoke: bool = False) -> list:
    rows: list = []
    _bench_overhead(rows, smoke=smoke)
    _bench_recovery(rows, smoke=smoke)
    for r in rows:
        emit(r["name"], r["us_per_call"], r["derived"])
    return rows


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
