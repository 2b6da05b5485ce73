#!/usr/bin/env python3
"""Smoke run of the PASSCoDe solver and its scoring engine on TPU chips.

One process drives the main path through its public entry points and
checks every phase against the repo's own references; any failed gate
raises, so the process exits nonzero and prints no result line.

One chip (no arguments), on the paper's rcv1 shape (Table 3: n = 677,399
train rows, 20,242 test rows, d = 47,236, 73 nonzeros per row, C = 1),
generated from ``--seed``:

  1. solve  — ``sharded_passcode_solve(use_kernel="auto", record=True)``;
     gaps finite and non-increasing, last gap ≤ 0.1·first, and the
     primal–dual invariant ‖ŵ − Σ αᵢxᵢ‖/‖ŵ‖ ≤ 1e-3 on the host in f64;
  2. kernels — each Pallas engine (dense, ELL, 2-D feature) compiled on
     the chip against its jnp engine at a shape its policy admits:
     ``tpu_custom_call`` in the HLO and (α, ŵ) equal to atol 1e-5; on
     the ELL shard the gap's one-hot pass is compiled and records the
     XLA path's gaps and backward errors to 1e-5 of g(0) = C·n and ‖ŵ‖;
  3. serving — ``ServeEngine`` on the phase-1 snapshot scores 256 test
     rows, each equal to the host f64 margin to 1e-4 relative;
  4. segmented — ``solve_segmented`` in two checkpointed segments,
     bit-identical to the phase-1 solve.

``--chips 4`` runs only the multi-chip path: the data=4 solve against
the same solve on a (data=4, model=1) mesh, which runs the same update
sequence through the 2-D engine, pod meshes against the
``cocoa_pod_solve`` oracle, and the rcv1 solve on data=4 under the
phase-1 gates.

Timings printed here are smoke timings of one run, not benchmark
metrics.  The last line of stdout is the JSON result.

    python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the paper's rcv1 (Table 3), at its published shape
RCV1 = dict(n_train=677_399, n_test=20_242, d=47_236, nnz_per_row=73, C=1.0)
KERNEL_ROWS = 8192  # phase-2 ELL / 2-D shard: rcv1 rows at n_loc = 8192
DENSE_SHAPE = (2048, 1024)  # phase-2 dense shard
N_SERVE = 256
EPOCHS = 6  # solver epochs; phase 4 splits them into two segments


class GateFailed(AssertionError):
    pass


def gate(ok: bool, what: str) -> None:
    if not ok:
        raise GateFailed(what)
    say(f"gate ok: {what}")


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent-cache hits, from its own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.cache_hits


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def host_w_of_alpha(ell, alpha, d) -> np.ndarray:
    idx = np.asarray(ell.indices).reshape(-1)
    vals = (np.asarray(alpha, np.float64)[:, None]
            * np.asarray(ell.values, np.float64)).reshape(-1)
    return np.bincount(idx, weights=vals, minlength=d + 1)[:d]


def host_margins(ell, w) -> np.ndarray:
    w1 = np.append(np.asarray(w, np.float64), 0.0)  # dummy slot for pads
    return (np.asarray(ell.values, np.float64)
            * w1[np.asarray(ell.indices)]).sum(axis=1)


def make_rcv1(seed: int):
    from repro.data.synthetic import DatasetRecipe, make_dataset

    r = RCV1
    recipe = DatasetRecipe("rcv1-full", r["n_train"], r["n_test"], r["d"],
                           r["nnz_per_row"], r["C"])
    t0 = time.perf_counter()
    ds = make_dataset("rcv1-full", seed=seed, recipe=recipe)
    say(f"generated rcv1-full train {ds.X_train.indices.shape} test "
        f"{ds.X_test.indices.shape} d={r['d']} in "
        f"{time.perf_counter() - t0:.1f} s (host)")
    return ds


def solve_gates(ds, res, *, label: str) -> None:
    """Phase-1 gates, shared by the one-chip and data=4 solves."""
    gaps = np.asarray(res.gaps, np.float64)
    say(f"{label}: recorded gaps {gaps.tolist()}")
    gate(bool(np.all(np.isfinite(gaps))), f"{label}: gaps finite")
    gate(bool(np.all(np.diff(gaps) <= 0)), f"{label}: gaps non-increasing")
    gate(gaps[-1] <= 0.1 * gaps[0],
         f"{label}: last gap {gaps[-1]:.6g} <= 0.1 * first {gaps[0]:.6g}")
    d = ds.X_train.n_features
    w = np.asarray(res.w_hat, np.float64)
    wa = host_w_of_alpha(ds.X_train, res.alpha, d)
    rel = float(np.linalg.norm(w - wa) / np.linalg.norm(w))
    gate(rel <= 1e-3, f"{label}: |w - w(alpha)|/|w| = {rel:.3g} <= 1e-3")
    acc = float(np.mean(host_margins(ds.X_test, w) > 0))
    say(f"{label}: test accuracy {acc:.4f} on {ds.X_test.n_rows} rows")


def timed_solve(clock, dev, fn, *, epochs: int, label: str):
    import jax

    c0, h0 = clock.read()
    t0 = time.perf_counter()
    res = fn()
    jax.block_until_ready((res.alpha, res.w_hat, res.gaps))
    wall = time.perf_counter() - t0
    c1, h1 = clock.read()
    say(f"{label}: engine {res.engine}, gap {res.gap_engine}; wall "
        f"{wall:.2f} s, of which "
        f"compile {c1 - c0:.2f} s ({h1 - h0} persistent-cache hits); "
        f"smoke timing {(wall - (c1 - c0)) / epochs:.3f} s/epoch "
        f"(not a metric); peak device memory {peak_bytes(dev)} B")
    return res


# ----------------------------------------------------------- one chip ----


def phase_solve(ds, clock, dev, *, epochs: int, seed: int):
    from repro.core import Hinge, sharded_passcode_solve

    res = timed_solve(
        clock, dev,
        lambda: sharded_passcode_solve(ds.X_train, Hinge(C=RCV1["C"]),
                                       epochs=epochs, seed=seed,
                                       use_kernel="auto", record=True),
        epochs=epochs, label="phase 1 solve")
    gate(not res.engine.endswith("interpret"),
         f"phase 1: engine {res.engine} is not interpret mode")
    solve_gates(ds, res, label="phase 1")
    return res


def _engine_run(X, loss, *, mesh, use_kernel: bool, epochs: int, seed: int):
    """One solve through the prepared-pipeline API, returning the result
    and the compiled HLO text of the exact program that ran."""
    from repro.core.sharded import (
        build_pipeline,
        finalize_state,
        init_pipeline_state,
        prepare_solver,
    )

    setup = prepare_solver(X, loss, mesh=mesh, use_kernel=use_kernel,
                           seed=seed)
    fn = build_pipeline(setup, epochs=epochs, total_epochs=epochs,
                        segmented=True)
    st = init_pipeline_state(setup, total_epochs=epochs)
    compiled = fn.lower(setup.X, setup.sq_norms, st, setup.Y).compile()
    out = compiled(setup.X, setup.sq_norms, st, setup.Y)
    return finalize_state(setup, out, epochs=epochs), compiled.as_text()


def phase_kernels(ds, *, seed: int):
    from repro.core import Hinge
    from repro.data.sparse import EllMatrix
    from repro.data.synthetic import DatasetRecipe, make_dataset
    from repro.dist.mesh import solver_mesh, solver_mesh_2d

    loss = Hinge(C=1.0)
    n, d = DENSE_SHAPE
    dense = make_dataset("dense", seed=seed, recipe=DatasetRecipe(
        "dense", n, 16, d, d, 1.0)).dense_train()
    rows = EllMatrix(ds.X_train.indices[:KERNEL_ROWS],
                     ds.X_train.values[:KERNEL_ROWS], RCV1["d"])
    # (name, matrix, mesh, whether the gap's one-hot pass runs: rcv1's
    # width on the 1-D ELL pipeline)
    cases = (
        (f"dense {n}x{d}", dense, solver_mesh("data"), False),
        (f"ell rcv1 rows n_loc={KERNEL_ROWS} d={RCV1['d']}", rows,
         solver_mesh("data"), True),
        (f"feature (1,1) mesh, rcv1 rows n_loc={KERNEL_ROWS}", rows,
         solver_mesh_2d(data=1, model=1), False),
    )
    for name, X, mesh, onehot in cases:
        t0 = time.perf_counter()
        fused, hlo = _engine_run(X, loss, mesh=mesh, use_kernel=True,
                                 epochs=2, seed=seed)
        ref, _ = _engine_run(X, loss, mesh=mesh, use_kernel=False,
                             epochs=2, seed=seed)
        say(f"phase 2 {name}: {fused.engine} (gap {fused.gap_engine}) vs "
            f"{ref.engine} (gap {ref.gap_engine}), "
            f"{time.perf_counter() - t0:.1f} s wall incl. compile")
        gate("/pallas" in fused.engine
             and fused.engine.endswith("-compiled"),
             f"phase 2 {name}: kernel compiled, not interpreted")
        gate("tpu_custom_call" in hlo,
             f"phase 2 {name}: tpu_custom_call in the compiled HLO")
        da = float(np.abs(np.asarray(fused.alpha)
                          - np.asarray(ref.alpha)).max())
        dw = float(np.abs(np.asarray(fused.w_hat)
                          - np.asarray(ref.w_hat)).max())
        gate(max(da, dw) <= 1e-5,
             f"phase 2 {name}: |d alpha| {da:.3g}, |d w| {dw:.3g} <= 1e-5")
        if not onehot:
            continue
        # the gap's one-hot MXU pass against XLA's scatter and gather:
        # exact to f32 on the chip, which interpret mode cannot show
        gate(fused.gap_engine == "pallas-onehot-compiled"
             and ref.gap_engine == "xla",
             f"phase 2 {name}: gap engines {fused.gap_engine} vs "
             f"{ref.gap_engine}")
        dg = float(np.abs(np.asarray(fused.gaps)
                          - np.asarray(ref.gaps)).max())
        de = float(np.abs(np.asarray(fused.eps)
                          - np.asarray(ref.eps)).max())
        g0 = loss.C * X.n_rows  # the zero-start gap
        w_norm = float(np.linalg.norm(np.asarray(ref.w_hat)))
        say(f"phase 2 {name}: recorded gaps differ by {dg:.3g} "
            f"(g(0) = {g0:.6g}), backward errors by {de:.3g} "
            f"(|w| = {w_norm:.6g})")
        gate(dg <= 1e-5 * g0 and de <= 1e-5 * w_norm,
             f"phase 2 {name}: gaps and backward errors of the one-hot "
             f"pass within 1e-5 of g(0) and |w| of XLA's")


def phase_serve(ds, res):
    from repro.serve import (
        ScoreOutcome,
        ServeEngine,
        SnapshotStore,
        snapshot_from_result,
    )

    test = ds.X_test
    cols = np.asarray(test.indices[:N_SERVE])
    vals = np.asarray(test.values[:N_SERVE])
    engine = ServeEngine(SnapshotStore(snapshot_from_result(res, 0)),
                         k_max=test.k_max, max_batch=64)
    # compile the fixed-shape scoring dispatch before the timed requests
    engine.submit(cols=cols[0], vals=vals[0], deadline_s=600.0)
    engine.step()
    engine.start()
    tickets = [engine.submit(cols=c, vals=v, deadline_s=30.0)
               for c, v in zip(cols, vals)]
    outs = [t.result(timeout=120.0) for t in tickets]
    engine.stop()
    gate(all(isinstance(o, ScoreOutcome) for o in outs),
         f"phase 3: all {N_SERVE} requests ended in a ScoreOutcome")
    w = np.append(np.asarray(res.w_hat, np.float64), 0.0)
    ref = (vals.astype(np.float64) * w[cols]).sum(axis=1)
    scale = (np.abs(vals.astype(np.float64) * w[cols])).sum(axis=1)
    got = np.array([o.score for o in outs])
    err = float(np.max(np.abs(got - ref) / np.maximum(scale, 1e-30)))
    lat = np.array([o.latency_s for o in outs]) * 1e3
    say(f"phase 3: latency p50 {np.percentile(lat, 50):.3f} ms, p99 "
        f"{np.percentile(lat, 99):.3f} ms (smoke timing, not a metric)")
    gate(err <= 1e-4, f"phase 3: scores vs host f64 margins, max relative "
                      f"error {err:.3g} <= 1e-4")


def phase_segmented(ds, res, clock, dev, *, epochs: int, seed: int):
    from repro.core import Hinge
    from repro.resilience import solve_segmented

    ckpt = os.path.join(ROOT, "out", "chip_smoke", "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    os.makedirs(ckpt)
    seg = timed_solve(
        clock, dev,
        lambda: solve_segmented(ds.X_train, Hinge(C=RCV1["C"]),
                                epochs=epochs,
                                checkpoint_every=epochs // 2,
                                ckpt_dir=ckpt, seed=seed,
                                use_kernel="auto").result,
        epochs=epochs, label="phase 4 segmented solve")
    for name in ("alpha", "w_hat", "gaps"):
        a = np.asarray(getattr(res, name))
        b = np.asarray(getattr(seg, name))
        gate(a.shape == b.shape and bool(np.array_equal(a, b)),
             f"phase 4: segmented {name} bit-identical to the one-dispatch "
             f"solve")


# --------------------------------------------------------- four chips ----


def phase_four_chips(ds, clock, devs, *, epochs: int, seed: int):
    import jax

    from repro.core import (
        Hinge,
        SquaredHinge,
        cocoa_pod_solve,
        sharded_passcode_solve,
    )
    from repro.data.sparse import dense_to_ell
    from repro.data.synthetic import make_dataset
    from repro.dist.mesh import make_mesh, solver_mesh, solver_mesh_3d

    A = np.asarray
    # the multi-device tests' shape: 102 rows of the tiny recipe (a row
    # tail at every device count used here)
    X = A(make_dataset("tiny", seed=seed).dense_train())[:102]
    ell = dense_to_ell(X)
    loss = Hinge(C=1.0)
    data4 = solver_mesh("data")
    kw = dict(epochs=3, block_size=8)
    data4_2d = make_mesh((4, 1), ("data", "model"), devices=devs[:4])
    r0 = sharded_passcode_solve(ell, loss, mesh=data4_2d, **kw)
    r1 = sharded_passcode_solve(ell, loss, mesh=data4, **kw)
    d1 = max(float(np.abs(A(r0.alpha) - A(r1.alpha)).max()),
             float(np.abs(A(r0.w_hat) - A(r1.w_hat)).max()))
    gate(d1 < 1e-5, f"data=4 vs (data=4, model=1): {d1:.3g} < 1e-5")
    gate(float(np.abs(A(r1.alpha)[96:]).sum()) > 0,
         "data=4: the padded row tail is trained")

    sq = SquaredHinge(1.0)
    kwp = dict(epochs=5, block_size=16, seed=0)
    for mesh, name in (
            (make_mesh((2, 1), ("pod", "data"), devices=devs[:2]),
             "(pod=2, data=1)"),
            (solver_mesh_3d(pod=2, data=1, model=2), "(pod=2, data=1, "
                                                     "model=2)")):
        for delay in (0, 1):
            r = sharded_passcode_solve(X, sq, mesh=mesh,
                                       pod_delay_rounds=delay, **kwp)
            o = cocoa_pod_solve(X, sq, n_pods=2, pod_delay_rounds=delay,
                                **kwp)
            dd = max(float(np.abs(A(r.alpha) - A(o.alpha)).max()),
                     float(np.abs(A(r.w_hat) - A(o.w)).max()))
            gate(dd <= 1e-5 + 1e-5 * float(np.abs(A(o.w)).max()),
                 f"{name} delay={delay} vs cocoa_pod_solve oracle: {dd:.3g}")
    pod22 = make_mesh((2, 2), ("pod", "data"))
    for delay in (0, 1):
        r = sharded_passcode_solve(X, sq, mesh=pod22,
                                   pod_delay_rounds=delay, **kwp)
        o = cocoa_pod_solve(X, sq, n_pods=2, pod_delay_rounds=delay, **kwp)
        gr, go = A(r.gaps), A(o.gaps)
        # data=2 inside each pod runs PASSCoDe blocks the data=1 oracle
        # does not replay: compared on solution quality.  The final gap
        # sits 1.107x / 1.139x the oracle's at delay 0 / 1 on the CPU and
        # on the chip alike; 1.25x leaves room for rounding, not for a
        # worse solve
        gate(bool(np.all(np.isfinite(gr))) and gr[-1] <= 1.25 * go[-1],
             f"(pod=2, data=2) delay={delay}: final gap {gr[-1]:.4g} <= "
             f"1.25 * oracle {go[-1]:.4g}")
    # the multi-pod test's staleness sweep on this mesh: synchronous
    # merges keep w == w(alpha) to float noise, and the recorded backward
    # error does not fall as the merge FIFO deepens
    eps = [float(np.mean(A(sharded_passcode_solve(
        X, sq, mesh=pod22, epochs=8, block_size=16, seed=0,
        pod_delay_rounds=delay).eps))) for delay in (0, 1, 2, 4)]
    gate(eps[0] < 1e-4, f"(pod=2, data=2) delay=0: mean eps {eps[0]:.3g} "
                        f"< 1e-4")
    gate(all(b >= a - 1e-4 for a, b in zip(eps, eps[1:])),
         f"(pod=2, data=2): mean eps over delays 0/1/2/4 "
         f"{[float(f'{e:.4g}') for e in eps]} non-decreasing")

    res = timed_solve(
        clock, devs[0],
        lambda: sharded_passcode_solve(ds.X_train, Hinge(C=RCV1["C"]),
                                       mesh=data4, epochs=epochs,
                                       seed=seed, use_kernel="auto",
                                       record=True),
        epochs=epochs, label="data=4 rcv1-full solve")
    held = sum(a.nbytes for X in (ds.X_train, ds.X_test)
               for a in (X.indices, X.values))
    say(f"data=4: the generated dataset ({held} B) is the caller's, on "
        f"device {ds.X_train.values.devices().pop().id}; the solver's "
        f"shards are built on the host and placed per device")
    for dv in jax.devices():
        stats = dv.memory_stats() or {}
        say(f"data=4: device {dv.id} bytes_in_use "
            f"{stats.get('bytes_in_use', -1)} peak "
            f"{stats.get('peak_bytes_in_use', -1)}")
    solve_gates(ds, res, label="data=4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); this script runs only on a chip",
              file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} devices, found {len(devs)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime import use_compile_cache

    say(f"compile cache {use_compile_cache(ROOT)}")
    say(f"devices {len(devs)} x {devs[0].device_kind}, jax {jax.__version__}")
    clock = CompileClock()
    t0 = time.perf_counter()
    ds = make_rcv1(args.seed)
    if args.chips == 4:
        phase_four_chips(ds, clock, devs, epochs=EPOCHS, seed=args.seed)
    else:
        res = phase_solve(ds, clock, devs[0], epochs=EPOCHS,
                          seed=args.seed)
        phase_kernels(ds, seed=args.seed)
        phase_serve(ds, res)
        phase_segmented(ds, res, clock, devs[0], epochs=EPOCHS,
                        seed=args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
