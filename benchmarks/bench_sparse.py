"""Sparse (ELL) fast path benchmark: dense-vs-ELL sharded epoch time at
paper-like sparsity profiles, plus the VMEM feasibility frontier that
motivates the path (DESIGN.md §9).

Two profiles mirror the paper's Table 3 density regimes at CPU-CI scale:

  rcv1-like    d=4096, k_max=7   → 0.17% dense (paper: d≈47k, 0.16%)
  news20-like  d=8192, k_max=3   → 0.04% dense (paper: d≈1.35M, 0.03%)

Per-update work is O(d) on the dense engines and O(k_max) on the ELL
engines, so the unfused jnp head-to-head directly measures the sparsity
win; the fused Pallas ELL engine runs in interpret mode off-TPU
(semantics validation + host-side throughput, as in bench_kernel).

Feasibility rows evaluate ``dcd_kernel_fits`` vs ``dcd_ell_kernel_fits``
at *real paper scale*: shapes the dense policy rejects and the ELL
policy admits are exactly the problems the sparse path unlocks.

``main()`` returns its rows so benchmarks/run.py persists them as
out/BENCH_sparse.json.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.duals import Hinge
from repro.core.sharded import _masked_block_perms, make_sharded_epoch
from repro.data.sparse import dense_to_ell
from repro.dist.mesh import (
    lane_pad,
    dcd_ell_kernel_fits,
    dcd_ell_kernel_vmem_bytes,
    dcd_kernel_fits,
    dcd_kernel_vmem_bytes,
    solver_mesh,
)

PROFILES = (
    # name, n, d, k_max (CPU-CI scale; density mirrors the paper dataset)
    ("rcv1-like", 2048, 4096, 7),
    ("news20-like", 1024, 8192, 3),
)


def _make_ell_profile(rng, n, d, k):
    """Label-folded rows with exactly k nonzeros, unit-capped norms."""
    dense = np.zeros((n, d), np.float32)
    for i in range(n):
        cols = rng.choice(d, size=k, replace=False)
        v = rng.standard_normal(k).astype(np.float32)
        dense[i, cols] = v / max(np.linalg.norm(v), 1.0)
    return dense


def _bench_profile(rows, name, n, d, k):
    rng = np.random.default_rng(7)
    dense = _make_ell_profile(rng, n, d, k)
    ell = dense_to_ell(dense)
    loss = Hinge(C=1.0)
    mesh = solver_mesh("data")
    p = mesh.shape["data"]
    block_size = 64
    n_loc = n // p
    n_blocks = n_loc // block_size
    blocks = _masked_block_perms(jax.random.PRNGKey(0), p, n_loc, n,
                                 n_blocks, block_size)
    blocks = blocks.reshape(p * n_blocks, block_size)
    alpha = jnp.zeros((n,), jnp.float32)
    density = k / d

    # dense unfused engine
    X = jnp.asarray(dense)
    sq = jnp.sum(X * X, axis=1)
    w = jnp.zeros((d,), jnp.float32)
    carry = jnp.zeros((d,), jnp.float32)
    fn = make_sharded_epoch(mesh, loss)
    t_dense = timeit(lambda: fn(X, sq, alpha, w, blocks, carry))
    rows.append({
        "name": f"sparse/{name}/dense_jnp/n={n},d={d},k={k}",
        "us_per_call": t_dense * 1e6,
        "derived": f"density={density:.4%}",
    })

    # ELL unfused engine — same blocks, O(k_max) per update
    cols = jnp.asarray(ell.indices)
    vals = jnp.asarray(ell.values)
    sq_e = ell.row_sq_norms()
    w_pad = jnp.zeros((d + 1,), jnp.float32)
    carry_e = jnp.zeros((d + 1,), jnp.float32)
    fn_e = make_sharded_epoch(mesh, loss, ell=True)
    t_ell = timeit(lambda: fn_e((cols, vals), sq_e, alpha, w_pad, blocks,
                                carry_e))
    rows.append({
        "name": f"sparse/{name}/ell_jnp/n={n},d={d},k={k}",
        "us_per_call": t_ell * 1e6,
        "derived": f"speedup_vs_dense={t_dense / t_ell:.2f}x",
    })

    # ELL fused engine (interpret mode off-TPU — semantics + host time)
    d1 = lane_pad(d + 1)
    w1 = jnp.zeros((d1,), jnp.float32)
    carry1 = jnp.zeros((d1,), jnp.float32)
    fn_k = make_sharded_epoch(mesh, loss, ell=True,
                              use_kernel=True)
    t_fused = timeit(lambda: fn_k((cols, vals), sq_e, alpha, w1,
                                  blocks, carry1))
    mode = "interpret" if jax.default_backend() != "tpu" else "compiled"
    rows.append({
        "name": f"sparse/{name}/ell_pallas/n={n},d={d},k={k}",
        "us_per_call": t_fused * 1e6,
        "derived": f"mode={mode}",
    })


def _bench_vmem_frontier(rows):
    """Paper-scale feasibility: what the ELL policy admits that the
    dense policy rejects (rcv1/news20/webspam at full Table-3 size)."""
    cases = (
        # name, n_loc, d, k_max — Table-3 sizes at a realistic device
        # count; webspam's d=16.6M padded primal alone exceeds VMEM, so
        # it stays rejected (that regime needs the 2D feature-sharded
        # solver, DESIGN.md §10 / bench_feature.py)
        ("rcv1-full-p64", 677_399 // 64, 47_236, 80),
        ("news20-full-p32", 19_996 // 32, 1_355_191, 550),
        ("webspam-full-p64", 350_000 // 64, 16_609_143, 400),
    )
    for name, n_loc, d, k in cases:
        dense_ok = dcd_kernel_fits(n_loc, d)
        ell_ok = dcd_ell_kernel_fits(d)
        rows.append({
            "name": f"sparse/vmem/{name}/n_loc={n_loc},d={d},k={k}",
            "us_per_call": 0.0,
            "derived": (
                f"dense_fits={dense_ok},ell_fits={ell_ok},"
                f"dense_mib={dcd_kernel_vmem_bytes(n_loc, d) / 2**20:.0f},"
                f"ell_mib={dcd_ell_kernel_vmem_bytes(d) / 2**20:.1f}"
            ),
        })


def main() -> list:
    rows: list = []
    for name, n, d, k in PROFILES:
        _bench_profile(rows, name, n, d, k)
    _bench_vmem_frontier(rows)
    for r in rows:
        emit(r["name"], r["us_per_call"], r["derived"])
    return rows


if __name__ == "__main__":
    main()
