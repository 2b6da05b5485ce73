"""Primal/dual objectives, duality gap, prediction accuracy.

Works on dense (n, d) data, ``EllMatrix`` or ``CsrMatrix``. Since rows
are label-folded (x_i = y_i·ẋ_i), classification is correct iff
wᵀx_i > 0, so binary accuracy needs no separate label vector.  The
multiclass helpers (``predict_multiclass``/``multiclass_accuracy``)
instead take a (K, d) one-vs-rest weight stack over *unfolded* rows and
integer class ids — the shapes the multi-task solver path produces
(DESIGN.md §16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data.sparse import (
    CsrMatrix,
    EllMatrix,
    csr_matvec,
    csr_rmatvec,
    ell_matvec,
    ell_rmatvec,
)

# Every f32 dot/matvec of the solvers runs at full f32 precision.  On CPU
# that is what a dot does anyway; on TPU the default precision rounds
# f32 operands to bf16 passes, which breaks the atol 1e-5 agreement of
# the fused, unfused and pipelined paths and the primal–dual invariant.
DOT_PRECISION = jax.lax.Precision.HIGHEST


def f32_dot(a, b):
    """``jnp.dot`` at ``DOT_PRECISION`` — the solvers' only dot."""
    return jnp.dot(a, b, precision=DOT_PRECISION)


def _matvec(X, w):
    if isinstance(X, EllMatrix):
        return ell_matvec(X, w)
    if isinstance(X, CsrMatrix):
        return csr_matvec(X, w)
    return f32_dot(X, w)


def _rmatvec(X, alpha):
    if isinstance(X, EllMatrix):
        return ell_rmatvec(X, alpha)
    if isinstance(X, CsrMatrix):
        return csr_rmatvec(X, alpha)
    return f32_dot(X.T, alpha)


def w_of_alpha(X, alpha):
    """w(α) = Σ_i α_i x_i  (eq. 3)."""
    return _rmatvec(X, alpha)


def primal_objective(w, X, loss):
    """P(w) = ½‖w‖² + Σ ℓ_i(wᵀx_i)  (eq. 1)."""
    z = _matvec(X, w)
    return 0.5 * f32_dot(w, w) + jnp.sum(loss.primal_loss(z))


def dual_objective(alpha, X, loss):
    """D(α) = ½‖Σ α_i x_i‖² + Σ ℓ*(−α_i)  (eq. 2)."""
    w = _rmatvec(X, alpha)
    return 0.5 * f32_dot(w, w) + jnp.sum(loss.conj(alpha))


def duality_gap(alpha, X, loss):
    """P(w(α)) + D(α) ≥ 0, → 0 at optimum (P(w*) = −D(α*))."""
    w = _rmatvec(X, alpha)
    return primal_objective(w, X, loss) + dual_objective(alpha, X, loss)


def perturbed_primal_objective(w, X, loss, eps):
    """Eq. (16): ½(w+ε)ᵀ(w+ε) + Σ ℓ_i(wᵀx_i) — the problem ŵ exactly
    solves under PASSCoDe-Wild (Corollary 1)."""
    z = _matvec(X, w)
    we = w + eps
    return 0.5 * f32_dot(we, we) + jnp.sum(loss.primal_loss(z))


def predict_accuracy(w, X):
    """Fraction of rows with wᵀx_i > 0 (x_i is label-folded)."""
    z = _matvec(X, w)
    return jnp.mean((z > 0).astype(jnp.float32))


def predict_multiclass(W, X):
    """Argmax class ids over a (K, d) one-vs-rest weight stack.

    ``X`` holds *unfolded* rows (multi-task solves share one X, so no
    label ever folded into it).  Returns (n,) int32 — row i is assigned
    to the head with the largest margin w_kᵀx_i.
    """
    W = jnp.asarray(W)
    if W.ndim != 2:
        raise ValueError(f"expected a (K, d) weight stack, got {W.shape}")
    scores = jax.vmap(lambda w: _matvec(X, w))(W)  # (K, n)
    return jnp.argmax(scores, axis=0).astype(jnp.int32)


def multiclass_accuracy(W, X, y_int):
    """Top-1 accuracy of the (K, d) stack against integer class ids."""
    pred = predict_multiclass(W, X)
    return jnp.mean((pred == jnp.asarray(y_int)).astype(jnp.float32))
