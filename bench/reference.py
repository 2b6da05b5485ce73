"""Plain host float64 references for the benchmark's output checks.

Straightforward numpy over the benchmark's own ELL arrays: nothing here
imports the program or takes a number the program made, except the
outputs under test (α, ŵ, a recorded gap, a score).  Conventions follow
the paper (Hsieh, Yu, Dhillon, ICML 2015), rows label-folded
(x_i = y_i·ẋ_i):

    P(w) = ½‖w‖² + Σ_i ℓ(wᵀx_i),   w(α) = Σ_i α_i x_i,
    gap(α) = P(w(α)) − D(α) = ‖w(α)‖² + Σ_i [ℓ(w(α)ᵀx_i) + ℓ*(−α_i)].
"""

from __future__ import annotations

import numpy as np


class Hinge:
    """ℓ(z) = C·max(1 − z, 0); ℓ*(−α) = −α on the box α ∈ [0, C]."""

    def __init__(self, C: float):
        self.C = float(C)

    def primal(self, z):
        return self.C * np.maximum(1.0 - z, 0.0)

    def conj(self, alpha):
        return -alpha

    def box_violation(self, alpha) -> float:
        """How far α lies outside its box (0 inside)."""
        a = np.asarray(alpha, np.float64)
        if not a.size:
            return 0.0
        return float(max(np.max(-a), np.max(a - self.C), 0.0))


LOSSES = {"hinge": Hinge}


def loss_of(cfg: dict):
    return LOSSES[cfg["loss"]](cfg["C"])


def zero_gap(cfg: dict) -> float:
    """gap(0) = Σ_i ℓ(0), in closed form: w(0) = 0 and ℓ*(0) = 0."""
    return float(cfg["n_train"]) * float(loss_of(cfg).primal(0.0))


def w_of_alpha(ids, vals, alpha, d: int) -> np.ndarray:
    """w(α) = Σ_i α_i x_i in float64."""
    contrib = (np.asarray(alpha, np.float64)[:, None]
               * np.asarray(vals, np.float64))
    return np.bincount(np.asarray(ids).reshape(-1),
                       weights=contrib.reshape(-1), minlength=d)[:d]


def margins(ids, vals, w) -> np.ndarray:
    """wᵀx_i for every row, float64."""
    w64 = np.asarray(w, np.float64)
    return (np.asarray(vals, np.float64) * w64[np.asarray(ids)]).sum(axis=1)


def duality_gap(ids, vals, alpha, d: int, loss) -> float:
    a = np.asarray(alpha, np.float64)
    w = w_of_alpha(ids, vals, a, d)
    z = margins(ids, vals, w)
    return float(w @ w + np.sum(loss.primal(z)) + np.sum(loss.conj(a)))


def solve_checks(ids, vals, d: int, loss, alpha, w_hat,
                 recorded_gap: float) -> dict:
    """The numbers one finished solve is held to:

    * ``inv``: ‖ŵ − w(α)‖ / ‖w(α)‖, the primal the engine and the psum
      maintained against the one its duals define;
    * ``box``: how far α lies outside its box;
    * ``gap``: |recorded gap − gap(α)| / gap(α), the device's stopping
      signal against the float64 gap of the same α."""
    a = np.asarray(alpha, np.float64)
    w = w_of_alpha(ids, vals, a, d)
    z = margins(ids, vals, w)
    g = float(w @ w + np.sum(loss.primal(z)) + np.sum(loss.conj(a)))
    nw = float(np.linalg.norm(w))
    inv = float(np.linalg.norm(np.asarray(w_hat, np.float64) - w)
                / max(nw, 1e-300))
    return {"inv": inv, "box": loss.box_violation(a),
            "gap": abs(float(recorded_gap) - g) / max(abs(g), 1e-300),
            "gap_host": g}


def score_errors(ids, vals, w, scores) -> np.ndarray:
    """|score − wᵀx| / Σ_j |x_j w_j| per request row, float64 — the
    error relative to the sum of the magnitudes it was summed from."""
    w64 = np.asarray(w, np.float64)
    prod = np.asarray(vals, np.float64) * w64[np.asarray(ids)]
    ref = prod.sum(axis=1)
    scale = np.maximum(np.abs(prod).sum(axis=1), 1e-300)
    return np.abs(np.asarray(scores, np.float64) - ref) / scale
