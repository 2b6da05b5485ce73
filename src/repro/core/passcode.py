"""PASSCoDe — Algorithm 2 with Lock / Atomic / Wild memory models.

XLA is deterministic SPMD, so true wall-clock races cannot occur.  We
instead *simulate the memory semantics deterministically* (seeded), which
is exactly what the paper's theory is about:

  * the algorithm proceeds in rounds of ``n_threads`` coordinate updates,
    one per thread, on disjoint coordinates (per-thread random
    permutation blocks, §3.3);
  * every thread computes Δα_t against a **stale view** ŵ of the primal
    vector: the round-start snapshot, optionally delayed by ``delay``
    extra rounds (staleness τ = n_threads·(delay+1) — Assumption 1 holds
    with U^j ⊇ Z^{j−τ});
  * write-back differs per memory model:
      - ``lock``:   updates are applied one-by-one inside the round, each
                    seeing all previous writes → serializable, identical
                    sequence to serial DCD (Algorithm 1);
      - ``atomic``: all Δα_t·x_t are **summed** into w — atomic adds never
                    lose increments (τ-stale reads, lossless writes);
      - ``wild``:   racing read-modify-writes: for a feature written by
                    ≥2 threads in the same round, with probability
                    ``conflict_rate`` the adds collide and only the last
                    scheduled writer's increment survives (seeded
                    last-writer-wins), losing the others — so the
                    maintained ŵ drifts from w̄ = Σ α_i x_i (eq. 6) and the
                    backward-error analysis of §4.2 applies.

The α update always lands (coordinates are owned by a single thread per
round), matching the paper: only w suffers memory conflicts.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objective import duality_gap, f32_dot, w_of_alpha
from repro.data.sparse import EllMatrix


class PasscodeResult(NamedTuple):
    alpha: jnp.ndarray  # α̂ — dual iterate
    w_hat: jnp.ndarray  # ŵ — the maintained primal vector (predict with this!)
    w_bar: jnp.ndarray  # w̄ = Σ α̂_i x_i (eq. 6)
    gaps: jnp.ndarray  # nominal duality gap per epoch (computed from w̄)
    eps_norms: jnp.ndarray  # ‖ε‖ = ‖w̄ − ŵ‖ per epoch
    epochs: int


def _round_indices(key, n, n_threads):
    """Disjoint per-thread coordinate streams: permute [n], reshape to
    (rounds, n_threads).  Truncates the ragged tail (< n_threads items)."""
    perm = jax.random.permutation(key, n)
    rounds = n // n_threads
    return perm[: rounds * n_threads].reshape(rounds, n_threads)


@functools.partial(
    jax.jit,
    static_argnames=("loss", "memory_model", "n_threads", "delay"),
)
def _passcode_epoch_dense(
    X,
    sq_norms,
    alpha,
    w_hat,
    rounds_idx,  # (rounds, p) int32
    round_keys,  # (rounds, 2) PRNG keys for wild conflicts
    loss,
    memory_model: str,
    n_threads: int,
    delay: int,
    conflict_rate: float,
):
    p = n_threads
    d = w_hat.shape[0]

    def lock_round(carry, inp):
        alpha, w, _hist = carry
        idx, _key = inp

        def body(k, ac):
            alpha, w = ac
            i = idx[k]
            x = X[i]
            delta = loss.delta(alpha[i], f32_dot(w, x), sq_norms[i])
            return alpha.at[i].add(delta), w + delta * x

        alpha, w = jax.lax.fori_loop(0, p, body, (alpha, w))
        return (alpha, w, _hist), ()

    def parallel_round(carry, inp):
        alpha, w, hist = carry  # hist: (delay, d) most-recent round deltas
        idx, key = inp
        # --- stale read: round-start snapshot, minus `delay` recent rounds.
        w_read = w - jnp.sum(hist, axis=0) if delay > 0 else w
        rows = X[idx]  # (p, d)
        wx = f32_dot(rows, w_read)  # (p,)
        deltas = jax.vmap(loss.delta)(alpha[idx], wx, sq_norms[idx])  # (p,)
        contrib = deltas[:, None] * rows  # (p, d)
        # --- write-back.
        summed = jnp.sum(contrib, axis=0)
        if memory_model == "atomic":
            w_delta = summed
        else:  # wild: seeded last-writer-wins on conflicted features
            korder, kconf = jax.random.split(key)
            position = jax.random.permutation(korder, p)  # schedule order
            writers = contrib != 0.0  # (p, d)
            n_writers = jnp.sum(writers, axis=0)  # (d,)
            # last scheduled writer per feature
            prio = jnp.where(writers, position[:, None], -1)  # (p, d)
            winner = jnp.argmax(prio, axis=0)  # (d,)
            lww = jnp.take_along_axis(contrib, winner[None, :], axis=0)[0]
            conflicted = (n_writers >= 2) & (
                jax.random.uniform(kconf, (d,)) < conflict_rate
            )
            w_delta = jnp.where(conflicted, lww, summed)
        w = w + w_delta
        alpha = alpha.at[idx].add(deltas)
        if delay > 0:
            hist = jnp.concatenate([hist[1:], w_delta[None]], axis=0)
        return (alpha, w, hist), ()

    hist0 = jnp.zeros((max(delay, 1), d), w_hat.dtype)
    step = lock_round if memory_model == "lock" else parallel_round
    (alpha, w_hat, _), _ = jax.lax.scan(
        step, (alpha, w_hat, hist0), (rounds_idx, round_keys)
    )
    return alpha, w_hat


@functools.partial(
    jax.jit,
    static_argnames=("loss", "memory_model", "n_threads", "delay", "n_features"),
)
def _passcode_epoch_ell(
    indices,
    values,
    sq_norms,
    alpha,
    w_pad,  # (d+1,)
    rounds_idx,
    round_keys,
    loss,
    memory_model: str,
    n_threads: int,
    delay: int,
    conflict_rate: float,
    n_features: int,
):
    p = n_threads
    d = n_features

    def lock_round(carry, inp):
        alpha, w_pad, _hist = carry
        idx, _key = inp

        def body(k, ac):
            alpha, w_pad = ac
            i = idx[k]
            ind, val = indices[i], values[i]
            wx = jnp.sum(w_pad[ind] * val)
            delta = loss.delta(alpha[i], wx, sq_norms[i])
            return alpha.at[i].add(delta), w_pad.at[ind].add(delta * val)

        alpha, w_pad = jax.lax.fori_loop(0, p, body, (alpha, w_pad))
        return (alpha, w_pad, _hist), ()

    def parallel_round(carry, inp):
        alpha, w_pad, hist = carry
        idx, key = inp
        w_read = w_pad - jnp.sum(hist, axis=0) if delay > 0 else w_pad
        ind = indices[idx]  # (p, k)
        val = values[idx]  # (p, k)
        wx = jnp.sum(w_read[ind] * val, axis=1)  # (p,)
        deltas = jax.vmap(loss.delta)(alpha[idx], wx, sq_norms[idx])
        contrib = deltas[:, None] * val  # (p, k)
        summed = (
            jnp.zeros_like(w_pad).at[ind].add(contrib)
        )  # padded slot d swallows padding
        if memory_model == "atomic":
            w_delta = summed
        else:
            korder, kconf = jax.random.split(key)
            position = jax.random.permutation(korder, p)
            # priority scatter-max: winner position per feature
            is_writer = contrib != 0.0
            prio_sparse = jnp.where(is_writer, position[:, None] + 1, 0)  # 1-based
            prio = (
                jnp.zeros((d + 1,), jnp.int32).at[ind].max(prio_sparse)
            )
            keep_lww = prio_sparse == prio[ind]  # this entry is the last writer
            lww = (
                jnp.zeros_like(w_pad)
                .at[ind]
                .add(jnp.where(keep_lww, contrib, 0.0))
            )
            n_writers = (
                jnp.zeros((d + 1,), jnp.int32)
                .at[ind]
                .add(is_writer.astype(jnp.int32))
            )
            conflicted = (n_writers >= 2) & (
                jax.random.uniform(kconf, (d + 1,)) < conflict_rate
            )
            w_delta = jnp.where(conflicted, lww, summed)
        w_pad = w_pad + w_delta
        alpha = alpha.at[idx].add(deltas)
        if delay > 0:
            hist = jnp.concatenate([hist[1:], w_delta[None]], axis=0)
        return (alpha, w_pad, hist), ()

    hist0 = jnp.zeros((max(delay, 1), d + 1), w_pad.dtype)
    step = lock_round if memory_model == "lock" else parallel_round
    (alpha, w_pad, _), _ = jax.lax.scan(
        step, (alpha, w_pad, hist0), (rounds_idx, round_keys)
    )
    return alpha, w_pad


def passcode_epoch(
    X,
    sq_norms,
    alpha,
    w_hat,
    key,
    loss,
    *,
    n_threads: int = 4,
    memory_model: str = "atomic",
    delay: int = 0,
    conflict_rate: float = 0.5,
):
    """One epoch (≈ n updates) of Algorithm 2 under the given memory model."""
    assert memory_model in ("lock", "atomic", "wild")
    n = X.n_rows if isinstance(X, EllMatrix) else X.shape[0]
    kperm, kround = jax.random.split(key)
    rounds_idx = _round_indices(kperm, n, n_threads)
    round_keys = jax.random.split(kround, rounds_idx.shape[0])
    if isinstance(X, EllMatrix):
        w_pad = jnp.concatenate([w_hat, jnp.zeros((1,), w_hat.dtype)])
        alpha, w_pad = _passcode_epoch_ell(
            X.indices, X.values, sq_norms, alpha, w_pad, rounds_idx, round_keys,
            loss, memory_model, n_threads, delay, conflict_rate, X.n_features,
        )
        return alpha, w_pad[:-1]
    return _passcode_epoch_dense(
        X, sq_norms, alpha, w_hat, rounds_idx, round_keys,
        loss, memory_model, n_threads, delay, conflict_rate,
    )


def passcode_solve(
    X,
    loss,
    *,
    n_threads: int = 4,
    memory_model: str = "atomic",
    epochs: int = 20,
    seed: int = 0,
    delay: int = 0,
    conflict_rate: float = 0.5,
    tol: float = 0.0,
    record: bool = True,
) -> PasscodeResult:
    """Run PASSCoDe-{Lock,Atomic,Wild} for `epochs` epochs."""
    n = X.n_rows if isinstance(X, EllMatrix) else X.shape[0]
    d = X.n_features if isinstance(X, EllMatrix) else X.shape[1]
    sq_norms = (
        X.row_sq_norms() if isinstance(X, EllMatrix) else jnp.sum(X * X, axis=1)
    )
    alpha = jnp.zeros((n,), jnp.float32)
    w_hat = jnp.zeros((d,), jnp.float32)
    key = jax.random.PRNGKey(seed)
    gaps, eps_norms = [], []
    done = 0
    for e in range(epochs):
        key, sub = jax.random.split(key)
        alpha, w_hat = passcode_epoch(
            X, sq_norms, alpha, w_hat, sub, loss,
            n_threads=n_threads, memory_model=memory_model,
            delay=delay, conflict_rate=conflict_rate,
        )
        done = e + 1
        if record:
            g = float(duality_gap(alpha, X, loss))
            w_bar = w_of_alpha(X, alpha)
            eps = float(jnp.linalg.norm(w_bar - w_hat))
            gaps.append(g)
            eps_norms.append(eps)
            if tol > 0 and g <= tol:
                break
    w_bar = w_of_alpha(X, alpha)
    return PasscodeResult(
        alpha, w_hat, w_bar, jnp.asarray(gaps), jnp.asarray(eps_norms), done
    )
