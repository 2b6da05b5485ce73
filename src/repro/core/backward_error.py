"""Backward-error analysis for PASSCoDe-Wild (paper §4.2, Thm 3, Cor 1).

At the Wild fixpoint the outputs (ŵ, α̂) generally violate eq. (3):
ŵ ≠ w̄ := Σ α̂_i x_i.  Theorem 3 says (α̂, w̄) solve a *perturbed* problem
whose perturbation is exactly ε = w̄ − ŵ, and Corollary 1 says ŵ is the
exact minimizer of ½(w+ε)ᵀ(w+ε) + Σℓ_i(wᵀx_i) — hence **predict with ŵ**.

The machine-checkable content of the theorem:

  (a) fixpoint residual: Δα from one more exact coordinate solve against
      ŵ is ~0 for every i, i.e. −ŵᵀx_i ∈ ∂ℓ*_i(−α̂_i); this is *the*
      optimality condition of the perturbed dual (14);
  (b) consequently ∇[perturbed primal](ŵ) = ŵ + ε − Σ α̂_i x_i = 0 holds
      *identically* once (a) holds, with −α̂_i the subgradient choice;
  (c) empirically: accuracy(ŵ) ≈ serial accuracy while accuracy(w̄)
      degrades with threads/conflict rate (Table 2).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.objective import (
    duality_gap,
    f32_dot,
    perturbed_primal_objective,
    predict_accuracy,
    primal_objective,
    w_of_alpha,
)
from repro.data.sparse import EllMatrix, ell_matvec


def _all_row_dots(X, w):
    if isinstance(X, EllMatrix):
        return ell_matvec(X, w)
    return f32_dot(X, w)


def fixpoint_residual(X, loss, alpha, w):
    """max_i |Δα_i| for one exact coordinate solve of (5) against w.

    Zero ⇔ (α, w) is a PASSCoDe fixpoint ⇔ −wᵀx_i ∈ ∂ℓ*(−α_i) ∀i
    (the optimality condition of the perturbed dual (14) with ε = w̄ − w).
    """
    sq = X.row_sq_norms() if isinstance(X, EllMatrix) else jnp.sum(X * X, axis=1)
    wx = _all_row_dots(X, w)
    deltas = jax.vmap(loss.delta)(alpha, wx, sq)
    return jnp.max(jnp.abs(deltas))


def backward_error_report(X, X_test, loss, result) -> Dict[str, Any]:
    """Full §4.2 report for a PasscodeResult (works for any memory model;
    for lock/atomic ε ≈ 0 and the report degenerates gracefully)."""
    alpha, w_hat = result.alpha, result.w_hat
    w_bar = w_of_alpha(X, alpha)
    eps = w_bar - w_hat
    report = {
        "eps_norm": float(jnp.linalg.norm(eps)),
        "w_bar_norm": float(jnp.linalg.norm(w_bar)),
        "w_hat_norm": float(jnp.linalg.norm(w_hat)),
        # (a) — perturbed-dual optimality (Thm 3).
        "fixpoint_residual_w_hat": float(fixpoint_residual(X, loss, alpha, w_hat)),
        # For contrast: the *nominal* residual against w̄ (nonzero for wild).
        "fixpoint_residual_w_bar": float(fixpoint_residual(X, loss, alpha, w_bar)),
        # Perturbed primal value at ŵ (Cor 1) vs nominal primal values.
        "perturbed_primal_at_w_hat": float(
            perturbed_primal_objective(w_hat, X, loss, eps)
        ),
        "primal_at_w_hat": float(primal_objective(w_hat, X, loss)),
        "primal_at_w_bar": float(primal_objective(w_bar, X, loss)),
        "nominal_duality_gap": float(duality_gap(alpha, X, loss)),
        # (c) — Table 2.
        "train_acc_w_hat": float(predict_accuracy(w_hat, X)),
        "train_acc_w_bar": float(predict_accuracy(w_bar, X)),
    }
    if X_test is not None:
        report["test_acc_w_hat"] = float(predict_accuracy(w_hat, X_test))
        report["test_acc_w_bar"] = float(predict_accuracy(w_bar, X_test))
    return report
