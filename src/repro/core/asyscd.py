"""AsySCD baseline (Liu & Wright, 2014; Liu et al., 2014).

Asynchronous stochastic (projected-gradient) coordinate descent on the
dual — *without* maintaining w.  Each coordinate step therefore needs
∇_i D(α) = x_iᵀ(Xᵀα) − 1 (hinge), an O(nnz) computation; the paper's §5
found AsySCD orders of magnitude slower than PASSCoDe for exactly this
reason (and O(n²) memory if Q = XXᵀ is materialized, which limited it to
news20).

Fidelity note: the original updates α_i ← Π(α_i − γ·∇_i D(α)/Q_ii) with
γ = 1/2, one stale gradient per update.  We recompute w̄ = Xᵀα once per
round of ``n_threads`` updates (a *stale* read for every thread in the
round — same staleness model as our PASSCoDe engine).  This is charitable
to AsySCD by a factor ≤ n_threads in cost yet it still loses badly, which
reproduces the paper's qualitative claim.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objective import duality_gap, f32_dot


class AsyscdResult(NamedTuple):
    alpha: jnp.ndarray
    gaps: jnp.ndarray
    epochs: int


@functools.partial(jax.jit, static_argnames=("loss", "n_threads"))
def _asyscd_epoch(X, sq_norms, alpha, rounds_idx, loss, n_threads, gamma):
    def round_step(alpha, idx):
        w_bar = f32_dot(X.T, alpha)  # no primal maintenance: O(nnz) per round
        rows = X[idx]
        grad = jax.vmap(loss.dual_grad)(alpha[idx], f32_dot(rows, w_bar))
        step = gamma * grad / jnp.maximum(sq_norms[idx], 1e-12)
        new = jax.vmap(loss.feasible)(alpha[idx] - step)
        return alpha.at[idx].set(new), ()

    alpha, _ = jax.lax.scan(round_step, alpha, rounds_idx)
    return alpha


def asyscd_solve(
    X,
    loss,
    *,
    n_threads: int = 4,
    epochs: int = 20,
    gamma: float = 0.5,
    seed: int = 0,
    record: bool = True,
) -> AsyscdResult:
    n = X.shape[0]
    sq_norms = jnp.sum(X * X, axis=1)
    alpha = jnp.zeros((n,), jnp.float32)
    key = jax.random.PRNGKey(seed)
    gaps = []
    rounds = n // n_threads
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, n)[: rounds * n_threads]
        rounds_idx = perm.reshape(rounds, n_threads)
        alpha = _asyscd_epoch(X, sq_norms, alpha, rounds_idx, loss, n_threads,
                              gamma)
        if record:
            gaps.append(float(duality_gap(alpha, X, loss)))
    return AsyscdResult(alpha, jnp.asarray(gaps), epochs)
