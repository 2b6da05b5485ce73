"""Plain host float64 references for the output checks of ragged rows.

``bench/reference.py``'s checks over the benchmark's own compressed
(CSR) arrays instead of ELL ones: the same numbers ``inv``, ``box`` and
``gap``, the same conventions (rows label-folded, x_i = y_i·ẋ_i), and
nothing of the program imported:

    w(α) = Σ_i α_i x_i,
    gap(α) = ‖w(α)‖² + Σ_i [ℓ(w(α)ᵀx_i) + ℓ*(−α_i)].
"""

from __future__ import annotations

import numpy as np

from bench.reference import loss_of, zero_gap  # noqa: F401  (re-exported)


def _rows(indptr) -> np.ndarray:
    """The row of each stored entry."""
    indptr = np.asarray(indptr, np.int64)
    return np.repeat(np.arange(indptr.size - 1), np.diff(indptr))


def w_of_alpha(ids, vals, indptr, alpha, d: int) -> np.ndarray:
    """w(α) = Σ_i α_i x_i in float64."""
    a = np.asarray(alpha, np.float64)
    return np.bincount(np.asarray(ids), minlength=d,
                       weights=a[_rows(indptr)]
                       * np.asarray(vals, np.float64))[:d]


def margins(ids, vals, indptr, w) -> np.ndarray:
    """wᵀx_i for every row, float64."""
    w64 = np.asarray(w, np.float64)
    n = np.asarray(indptr).size - 1
    return np.bincount(_rows(indptr), minlength=n,
                       weights=np.asarray(vals, np.float64)
                       * w64[np.asarray(ids)])


def duality_gap(ids, vals, indptr, alpha, d: int, loss) -> float:
    a = np.asarray(alpha, np.float64)
    w = w_of_alpha(ids, vals, indptr, a, d)
    z = margins(ids, vals, indptr, w)
    return float(w @ w + np.sum(loss.primal(z)) + np.sum(loss.conj(a)))


def solve_checks(ids, vals, indptr, d: int, loss, alpha, w_hat,
                 recorded_gap: float) -> dict:
    """``reference.solve_checks`` over CSR rows: ``inv`` = ‖ŵ − w(α)‖ /
    ‖w(α)‖, ``box`` = how far α lies outside its box, ``gap`` =
    |recorded gap − gap(α)| / gap(α)."""
    a = np.asarray(alpha, np.float64)
    w = w_of_alpha(ids, vals, indptr, a, d)
    z = margins(ids, vals, indptr, w)
    g = float(w @ w + np.sum(loss.primal(z)) + np.sum(loss.conj(a)))
    nw = float(np.linalg.norm(w))
    inv = float(np.linalg.norm(np.asarray(w_hat, np.float64) - w)
                / max(nw, 1e-300))
    return {"inv": inv, "box": loss.box_violation(a),
            "gap": abs(float(recorded_gap) - g) / max(abs(g), 1e-300),
            "gap_host": g}
