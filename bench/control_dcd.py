"""The solve cells' control: the plain dual coordinate descent of the
paper (Hsieh, Yu, Dhillon, ICML 2015, Algorithm 1, hinge loss) with its
state (α, w) and its products in bfloat16, the precision one step below
the configurations' float32; sums are taken in float32.

It imports nothing of the program.  ``bench/drivers/solve.py`` puts it in
the program's place on the cell's own data: one update per row in an
order drawn from the seed, one epoch per dispatch, and the gap recorded
after each, computed in the same precision.  Its α and w then go to the
same output checks as the program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
# sound solves reach the target in 4 to 5 epochs (PERF.md); the control
# stops there or after this many epochs, whichever comes first
EPOCHS = 6


def zero_state(n: int, d: int):
    """α = 0 and w = w(0) = 0."""
    return jnp.zeros((n,), DTYPE), jnp.zeros((d,), DTYPE)


def make_epoch(ids, vals, C: float):
    """``epoch(alpha, w, key) -> (alpha, w, gap)`` over the label-folded
    ELL rows (``ids``, ``vals``): every row once, in the order
    ``permutation(key)``, then gap = ‖w‖² + Σ_i [ℓ(wᵀx_i) − α_i]."""
    n = ids.shape[0]
    x_all = jnp.asarray(vals, DTYPE)
    sq = jnp.sum(jnp.square(x_all.astype(jnp.float32)), axis=1)

    def update(t, carry):
        alpha, w, perm = carry
        i = perm[t]
        idx, x = ids[i], x_all[i]
        grad = jnp.sum((w[idx] * x).astype(jnp.float32)) - 1.0
        old = alpha[i]
        new = jnp.clip(old.astype(jnp.float32) - grad / sq[i], 0.0,
                       C).astype(DTYPE)
        return alpha.at[i].set(new), w.at[idx].add((new - old) * x), perm

    @jax.jit
    def epoch(alpha, w, key):
        perm = jax.random.permutation(key, n)
        alpha, w, _ = jax.lax.fori_loop(0, n, update, (alpha, w, perm))
        z = jnp.sum((w[ids] * x_all).astype(jnp.float32), axis=1)
        w32 = w.astype(jnp.float32)
        gap = (w32 @ w32 + C * jnp.sum(jnp.maximum(1.0 - z, 0.0))
               - jnp.sum(alpha.astype(jnp.float32)))
        return alpha, w, gap

    return epoch
