"""Serial Stochastic Dual Coordinate Descent — Algorithm 1 (LIBLINEAR).

The inner loop maintains w(α) = Σ α_i x_i so one update costs O(nnz/n)
(sparse) / O(d) (dense).  Index order is a random permutation per epoch
(paper §3.3 "Random Permutation": sampling without replacement).

Supports dense (n, d) arrays, ``EllMatrix`` and ``CsrMatrix`` (ragged
rows: the epoch runs them padded to the longest, which is fine at the
sizes an oracle runs; the gap reads the rows unpadded).  The dense path is the
readable reference; the ELL path is what the distributed/Pallas layers
build on.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objective import duality_gap, f32_dot, w_of_alpha
from repro.data.sparse import CsrMatrix, EllMatrix, pad_primal, unpad_primal


class DcdState(NamedTuple):
    alpha: jnp.ndarray  # (n,)
    w: jnp.ndarray  # (d,) — maintained primal (eq. 3)


@functools.partial(jax.jit, static_argnames=("loss",))
def _dcd_epoch_dense(X, sq_norms, state: DcdState, perm, loss) -> DcdState:
    def body(k, carry):
        alpha, w = carry
        i = perm[k]
        x = X[i]
        wx = f32_dot(w, x)
        delta = loss.delta(alpha[i], wx, sq_norms[i])
        alpha = alpha.at[i].add(delta)
        w = w + delta * x
        return alpha, w

    alpha, w = jax.lax.fori_loop(0, perm.shape[0], body, tuple(state))
    return DcdState(alpha, w)


@functools.partial(jax.jit, static_argnames=("loss", "n_features"))
def _dcd_epoch_ell(indices, values, sq_norms, alpha, w_pad, perm, loss, n_features):
    def body(k, carry):
        alpha, w_pad = carry
        i = perm[k]
        idx = indices[i]
        val = values[i]
        wx = jnp.sum(w_pad[idx] * val)
        delta = loss.delta(alpha[i], wx, sq_norms[i])
        alpha = alpha.at[i].add(delta)
        w_pad = w_pad.at[idx].add(delta * val)
        return alpha, w_pad

    alpha, w_pad = jax.lax.fori_loop(0, perm.shape[0], body, (alpha, w_pad))
    return alpha, w_pad


def dcd_epoch(X, sq_norms, state: DcdState, perm, loss) -> DcdState:
    """One epoch (n coordinate updates in `perm` order)."""
    if isinstance(X, EllMatrix):
        w_pad = pad_primal(state.w)
        alpha, w_pad = _dcd_epoch_ell(
            X.indices, X.values, sq_norms, state.alpha, w_pad, perm, loss,
            X.n_features,
        )
        return DcdState(alpha, unpad_primal(w_pad))
    return _dcd_epoch_dense(X, sq_norms, state, perm, loss)


class DcdResult(NamedTuple):
    alpha: jnp.ndarray
    w: jnp.ndarray
    gaps: jnp.ndarray  # duality gap after each epoch
    epochs: int


def dcd_solve(
    X,
    loss,
    *,
    epochs: int = 20,
    seed: int = 0,
    tol: float = 0.0,
    alpha0=None,
    record_gap: bool = True,
) -> DcdResult:
    """Run serial DCD for `epochs` epochs (early-stop on duality gap ≤ tol)."""
    X_rows = X  # the gap reads the rows unpadded
    if isinstance(X, CsrMatrix):
        X = X.to_ell()
    n = X.n_rows if isinstance(X, EllMatrix) else X.shape[0]
    d = X.n_features if isinstance(X, EllMatrix) else X.shape[1]
    sq_norms = (
        X.row_sq_norms() if isinstance(X, EllMatrix) else jnp.sum(X * X, axis=1)
    )
    alpha = (
        jnp.zeros((n,), jnp.float32) if alpha0 is None else loss.feasible(alpha0)
    )
    w = w_of_alpha(X, alpha) if alpha0 is not None else jnp.zeros((d,), jnp.float32)
    state = DcdState(alpha, w)
    key = jax.random.PRNGKey(seed)
    gaps = []
    done = 0
    for e in range(epochs):
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, n)
        state = dcd_epoch(X, sq_norms, state, perm, loss)
        done = e + 1
        if record_gap:
            g = float(duality_gap(state.alpha, X_rows, loss))
            gaps.append(g)
            if tol > 0 and g <= tol:
                break
    return DcdResult(state.alpha, state.w, jnp.asarray(gaps), done)
