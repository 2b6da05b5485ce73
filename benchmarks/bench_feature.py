"""2D (data × model) feature-sharded solver benchmark (DESIGN.md §10):

1. **d-sweep** — 1D replicated-primal vs 2D feature-sharded epoch time
   at equal device count, on the devices this process sees.  The 1D path
   pays O(d) per round (full-primal psum + update) regardless of
   sparsity; the 2D path pays O(d/m) plus per-update scalar psums, so
   the crossover moves toward 2D as d grows — the webspam/kddb regime.
2. **VMEM frontier** — which (n, d, density, m) shapes each admission
   policy (`dcd_kernel_fits` dense, `dcd_ell_kernel_fits` 1D ELL,
   `dcd_feature_kernel_fits` 2D) accepts, at real paper Table-3 scale.
   The headline entry: webspam's d≈16.6M at m=16 is admitted *only* by
   the feature-sharded policy — the replicated padded primal alone
   exceeds VMEM for both 1D policies.

``main()`` returns its rows so benchmarks/run.py persists them as
out/BENCH_feature.json.
"""

from __future__ import annotations

from benchmarks.common import emit
from repro.dist.mesh import (
    dcd_ell_kernel_fits,
    dcd_ell_kernel_vmem_bytes,
    dcd_feature_kernel_fits,
    dcd_feature_kernel_vmem_bytes,
    dcd_kernel_fits,
    dcd_kernel_vmem_bytes,
)

D_SWEEP = (131_072, 1_048_576, 4_194_304)


def _run_sweep(rows):
    """1D replicated primal vs 2D feature-sharded epoch, on every device
    this process sees: ``data=p`` against ``(data=p/m, model=m)`` with
    the widest m ∈ {4, 2, 1} dividing p.  Runs in this process, so on a
    chip host it holds the chip itself; a failure propagates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import timeit
    from repro.core.duals import Hinge
    from repro.core.sharded import (
        _masked_block_perms,
        make_sharded_epoch,
        make_sharded_epoch_2d,
    )
    from repro.data.sparse import EllMatrix, ell_column_split
    from repro.dist.mesh import solver_mesh, solver_mesh_2d
    from repro.dist.sharding import named, replicated

    N, K, B = 256, 8, 32
    loss = Hinge(C=1.0)
    rng = np.random.default_rng(7)
    p1 = len(jax.devices())
    m2 = next(m for m in (4, 2, 1) if p1 % m == 0)
    p2 = p1 // m2
    mesh1 = solver_mesh("data")
    mesh2 = solver_mesh_2d(model=m2)

    for d in D_SWEEP:
        idx = np.stack([rng.choice(d, size=K, replace=False)
                        for _ in range(N)]).astype(np.int32)
        v = rng.standard_normal((N, K)).astype(np.float32)
        v /= np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1.0)
        ell = EllMatrix(jnp.asarray(idx), jnp.asarray(v), d)
        sq = ell.row_sq_norms()
        alpha = jnp.zeros((N,), jnp.float32)

        # ---- 1D replicated primal (the ELL path) ----
        blocks1 = _masked_block_perms(jax.random.PRNGKey(0), p1, N // p1,
                                      N, max(N // p1 // B, 1), B)
        blocks1 = jax.device_put(
            blocks1.reshape(-1, B), named(mesh1, "data"))
        X1 = (jax.device_put(ell.indices, named(mesh1, "data", None)),
              jax.device_put(ell.values, named(mesh1, "data", None)))
        sq1 = jax.device_put(sq, named(mesh1, "data"))
        a1 = jax.device_put(alpha, named(mesh1, "data"))
        w1 = jax.device_put(jnp.zeros((d + 1,), jnp.float32),
                            replicated(mesh1))
        c1 = jax.device_put(jnp.zeros((d + 1,), jnp.float32),
                            replicated(mesh1))
        fn1 = make_sharded_epoch(mesh1, loss, ell=True)
        t1 = timeit(lambda: fn1(X1, sq1, a1, w1, blocks1, c1))
        rows.append(dict(
            name=f"feature/sweep_1d_replicated/n={N},d={d},p={p1}",
            us_per_call=t1 * 1e6,
            derived=f"primal_words_per_device={d + 1}"))

        # ---- 2D feature-sharded ----
        fse = ell_column_split(ell, m2)
        d1_loc = fse.d_loc + 1
        n_loc = N // p2
        blocks2 = _masked_block_perms(jax.random.PRNGKey(0), p2, n_loc,
                                      N, max(n_loc // B, 1), B)
        blocks2 = jax.device_put(
            blocks2.reshape(-1, B), named(mesh2, "data"))
        X2 = (jax.device_put(fse.indices,
                             named(mesh2, "data", "model", None)),
              jax.device_put(fse.values,
                             named(mesh2, "data", "model", None)))
        sq2 = jax.device_put(sq, named(mesh2, "data"))
        a2 = jax.device_put(alpha, named(mesh2, "data"))
        w2 = jax.device_put(jnp.zeros((m2 * d1_loc,), jnp.float32),
                            named(mesh2, "model"))
        c2 = jax.device_put(jnp.zeros((m2 * d1_loc,), jnp.float32),
                            named(mesh2, "model"))
        fn2 = make_sharded_epoch_2d(mesh2, loss)
        t2 = timeit(lambda: fn2(X2, sq2, a2, w2, blocks2, c2))
        rows.append(dict(
            name=f"feature/sweep_2d_sharded/n={N},d={d},p={p2},m={m2}",
            us_per_call=t2 * 1e6,
            derived=(f"primal_words_per_device={d1_loc},"
                     f"speedup_vs_1d={t1 / t2:.2f}x")))


def _vmem_frontier(rows):
    """Admission table at real Table-3 scale: (n, p) fixes n_loc, k the
    row density, m the model-axis width; the 2D policy sees the
    per-shard (k_loc, d_loc) shapes."""
    cases = (
        # name, n, p, d, k, m
        ("rcv1-full", 677_399, 64, 47_236, 80, 4),
        ("news20-full", 19_996, 32, 1_355_191, 550, 8),
        ("webspam-full", 350_000, 64, 16_609_143, 400, 16),
        ("kddb-full", 19_264_097, 2048, 29_890_095, 100, 64),
    )
    for name, n, p, d, k, m in cases:
        n_loc = -(-n // p)
        k_loc = -(-k // m)
        d_loc = -(-d // m)
        dense_ok = dcd_kernel_fits(n_loc, d)
        ell_ok = dcd_ell_kernel_fits(d)
        feat_ok = dcd_feature_kernel_fits(n_loc, k_loc, d_loc)
        rows.append({
            "name": (f"feature/vmem/{name}/n_loc={n_loc},d={d},"
                     f"k={k},m={m}"),
            "us_per_call": 0.0,
            "derived": (
                f"dense_fits={dense_ok},ell_fits={ell_ok},"
                f"feature_fits={feat_ok},"
                f"density={k / d:.5%},"
                f"dense_mib={dcd_kernel_vmem_bytes(n_loc, d) / 2**20:.0f},"
                f"ell_mib={dcd_ell_kernel_vmem_bytes(d) / 2**20:.1f},"
                f"feature_mib="
                f"{dcd_feature_kernel_vmem_bytes(n_loc, k_loc, d_loc) / 2**20:.1f}"
            ),
        })


def main() -> list:
    rows: list = []
    _run_sweep(rows)
    _vmem_frontier(rows)
    for r in rows:
        emit(r["name"], r["us_per_call"], r["derived"])
    return rows


if __name__ == "__main__":
    main()
