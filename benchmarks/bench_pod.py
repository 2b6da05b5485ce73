"""Pod-scale double-async solver benchmark (DESIGN.md §13): the
convergence-vs-staleness trade the ``pod_delay_rounds`` knob buys, plus
the mesh-overhead cost of carrying the ``pod`` axis at all.

Section 1 (semantics, not perf): the serial ``cocoa_pod_solve`` oracle
sweeps ``pod_delay_rounds`` ∈ {0, 1, 2, 4} at a fixed pod count and
records, per staleness level, the final duality gap and the mean
backward error ε = ‖w(α) − ŵ‖ against the stale merged read view —
Table 2's staleness→ε relationship as numbers in a committed artifact.
Delay 0 is a synchronous CoCoA outer round (ε is float noise); every
extra in-flight merge round grows ε and degrades — boundedly — the gap
at equal epochs.

Section 2 (overhead): the SPMD pipeline built on a ``(pod=1, data=p)``
mesh runs the *same* update sequence as the plain ``("data",)`` mesh
build, so the timed ratio between them is the pure cost of the pod
machinery (outer merge scan + pod-axis psum collectives) with zero
algorithmic difference.  When the host has ≥ 2 devices a real
``(2, p//2)`` row is added alongside.

``main()`` returns rows for benchmarks/run.py to persist as
BENCH_pod.json (each row stamped with backend + interpret-vs-compiled
mode); ``--smoke`` shrinks everything to a CI-budget sanity pass.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.cocoa import cocoa_pod_solve
from repro.core.duals import Hinge
from repro.core.sharded import _n_blocks, make_sharded_pipeline
from repro.data.sparse import EllMatrix
from repro.dist.mesh import make_mesh, solver_mesh
from repro.dist.sharding import named, replicated


def _make_dense(rng, n, d):
    X = rng.standard_normal((n, d)).astype(np.float32)
    X /= np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1.0)
    return X


def _make_ell(rng, n, d, k):
    idx = np.stack([rng.choice(d, size=k, replace=False)
                    for _ in range(n)]).astype(np.int32)
    v = rng.standard_normal((n, k)).astype(np.float32)
    v /= np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1.0)
    return EllMatrix(jnp.asarray(idx), jnp.asarray(v), d)


def _bench_staleness(rows, *, smoke: bool):
    """Oracle convergence-vs-staleness sweep: gap + ε per delay."""
    n, d, pods = (128, 64, 2) if smoke else (384, 128, 4)
    epochs = 4 if smoke else 10
    delays = (0, 2) if smoke else (0, 1, 2, 4)
    loss = Hinge(C=1.0)
    X = _make_dense(np.random.default_rng(7), n, d)
    for delay in delays:
        t0 = time.perf_counter()
        o = jax.block_until_ready(cocoa_pod_solve(
            X, loss, n_pods=pods, epochs=epochs, block_size=32,
            pod_delay_rounds=delay, seed=0))
        t = time.perf_counter() - t0
        gaps = np.asarray(o.gaps)
        eps = np.asarray(o.eps)
        eps_s = "->".join(f"{e:.3g}" for e in eps)
        rows.append({
            "name": (f"pod/staleness/pods={pods},delay={delay}/"
                     f"n={n},d={d}"),
            "us_per_call": t * 1e6,
            "derived": (f"epochs={epochs},final_gap={gaps[-1]:.4g},"
                        f"mean_eps={eps.mean():.4g},eps={eps_s}"),
        })


def _bench_overhead(rows, *, smoke: bool):
    """Plain ("data",) mesh vs pod meshes running identical math."""
    n, d, k = (256, 512, 7) if smoke else (1024, 2048, 7)
    epochs, block_size = (3, 32) if smoke else (8, 64)
    loss = Hinge(C=1.0)
    n_dev = len(jax.devices())
    meshes = [("plain", solver_mesh("data"))]
    meshes.append(("pod1", make_mesh((1, n_dev), ("pod", "data"))))
    if n_dev >= 2 and n_dev % 2 == 0:
        meshes.append(
            ("pod2", make_mesh((2, n_dev // 2), ("pod", "data"))))
    ell = _make_ell(np.random.default_rng(11), n, d, k)
    times = {}
    for name, mesh in meshes:
        pod_on = "pod" in mesh.axis_names
        pods = mesh.shape["pod"] if pod_on else 1
        row_ax = ("pod", "data") if pod_on else "data"
        n_blocks = _n_blocks(-(-n // pods), block_size)
        X = (jax.device_put(ell.indices, named(mesh, row_ax, None)),
             jax.device_put(ell.values, named(mesh, row_ax, None)))
        sq = jax.device_put(ell.row_sq_norms(), named(mesh, row_ax))
        zeros_n = jax.device_put(jnp.zeros((n,), jnp.float32),
                                 named(mesh, row_ax))
        zeros_d = jax.device_put(jnp.zeros((d + 1,), jnp.float32),
                                 replicated(mesh))
        key = jax.random.PRNGKey(0)
        fn = make_sharded_pipeline(
            mesh, loss, epochs=epochs, block_size=block_size,
            n_blocks=n_blocks, n_rows=n, ell=True, record=True,
            gap_every=epochs)
        times[name] = timeit(fn, X, sq, zeros_n, zeros_d, key, zeros_d,
                             warmup=1, iters=3)
    base = times["plain"]
    for name, mesh in meshes:
        shape = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
        rows.append({
            "name": f"pod/overhead/{name}/mesh={shape},n={n},d={d}",
            "us_per_call": times[name] * 1e6,
            "derived": (f"epochs={epochs},"
                        f"vs_plain={times[name] / base:.3f}x"),
        })


def main(smoke: bool = False) -> list:
    rows: list = []
    _bench_staleness(rows, smoke=smoke)
    _bench_overhead(rows, smoke=smoke)
    for r in rows:
        emit(r["name"], r["us_per_call"], r["derived"])
    return rows


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
