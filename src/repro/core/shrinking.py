"""Shrinking heuristic (paper §3.3; Hsieh et al. 2008).

LIBLINEAR skips coordinates that look pinned at a bound.  Data-dependent
control flow is hostile to XLA, so we keep fixed shapes and use an
*active mask*: a coordinate is frozen when it sits at a bound with a
projected gradient pointing out of the box by more than ``shrink_tol``;
frozen coordinates take a zero-delta update (masked).

The mask is recomputed every ``shrink_every`` epochs from fresh
gradients, which restores wrongly-shrunk coordinates between recompute
points, and the final epoch always runs a *full* unmasked pass — the
direct analogue of LIBLINEAR's "unshrink and reoptimize once the
shrunk problem converges" safeguard, so a coordinate frozen by a stale
gradient right before the end still gets its exact update.

``dcd_solve_shrink`` is the **serial reference** the distributed solver
is tested against (DESIGN.md §12): it draws each epoch's permutation
through the same PRNG chain as ``repro.core.sharded._device_block_perm``
at p = 1 (``key, sub = split(key)`` then ``permutation(split(sub, 1)[0],
n)``), maintains the primal through the updates exactly like the sharded
engines (no ``w_of_alpha`` recompute), and applies the same
mask-recompute / final-full-pass schedule — so
``sharded_passcode_solve(..., shrink_every=k)`` on a single device with
``block_size=n`` runs the bit-identical update sequence
(``tests/test_sharded_shrink.py`` pins agreement at atol 1e-5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.duals import Hinge, SquaredHinge
from repro.core.objective import duality_gap, f32_dot


def active_mask(loss, alpha, grads, shrink_tol: float):
    """True where the coordinate must stay active.

    Elementwise over any shape, so it runs unchanged on a device's local
    α shard inside a ``shard_map`` body (the sharded solver's per-device
    mask recompute) as on the full serial vector."""
    if isinstance(loss, Hinge):
        at_lo = (alpha <= 0.0) & (grads > shrink_tol)
        at_hi = (alpha >= loss.C) & (grads < -shrink_tol)
        return ~(at_lo | at_hi)
    if isinstance(loss, SquaredHinge):
        return ~((alpha <= 0.0) & (grads > shrink_tol))
    return jnp.ones_like(alpha, bool)  # logistic: interior — never shrink


def active_mask_from_w(loss, alpha, wx, shrink_tol: float):
    """``active_mask`` from the per-row dot products ``wx = wᵀx_i``
    instead of precomputed gradients — the form every engine can feed
    directly (serial: X @ w; ELL: gather-dot; 2-D: model-axis psum)."""
    return active_mask(loss, alpha, loss.dual_grad(alpha, wx), shrink_tol)


@functools.partial(jax.jit, static_argnames=("loss",))
def _shrink_epoch(X, sq_norms, alpha, w, perm, mask, loss):
    def body(k, carry):
        alpha, w = carry
        i = perm[k]
        x = X[i]
        delta = jnp.where(
            mask[i], loss.delta(alpha[i], f32_dot(w, x), sq_norms[i]), 0.0
        )
        return alpha.at[i].add(delta), w + delta * x

    alpha, w = jax.lax.fori_loop(0, perm.shape[0], body, (alpha, w))
    return alpha, w


def dcd_solve_shrink(
    X, loss, *, epochs: int = 20, seed: int = 0, shrink_tol: float = 1e-3,
    shrink_every: int = 1, unshrink: bool = True,
):
    """Serial DCD with the shrinking mask; returns (alpha, w, gaps,
    active_fraction_per_epoch).

    ``w`` is the *maintained* primal carried through the updates (the
    same object every sharded engine carries), not a ``w_of_alpha``
    recompute — with masked zero-delta updates the two are equal anyway
    (a frozen coordinate adds 0·x), but returning the maintained vector
    makes this the drop-in equivalence baseline for the distributed
    masked paths.  ``unshrink=True`` (default) forces the final epoch to
    run unmasked — LIBLINEAR's final-full-pass semantics."""
    n, d = X.shape
    shrink_every = max(int(shrink_every), 1)
    sq_norms = jnp.sum(X * X, axis=1)
    alpha = jnp.zeros((n,), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    key = jax.random.PRNGKey(seed)
    mask = jnp.ones((n,), bool)
    gaps, act = [], []
    for e in range(epochs):
        key, sub = jax.random.split(key)
        # the p=1 draw of the sharded solver's _device_block_perm: one
        # per-device subkey, full local permutation — bit-matching the
        # single-device block_size=n sequence
        perm = jax.random.permutation(jax.random.split(sub, 1)[0], n)
        if e % shrink_every == 0:
            wx = f32_dot(X, w)
            mask = active_mask_from_w(loss, alpha, wx, shrink_tol)
        run_mask = mask
        if unshrink and e == epochs - 1:
            run_mask = jnp.ones((n,), bool)  # final full pass
        alpha, w = _shrink_epoch(X, sq_norms, alpha, w, perm, run_mask,
                                 loss)
        gaps.append(float(duality_gap(alpha, X, loss)))
        act.append(float(jnp.mean(mask.astype(jnp.float32))))
    return alpha, w, jnp.asarray(gaps), jnp.asarray(act)
