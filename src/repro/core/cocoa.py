"""CoCoA baseline (Jaggi et al., 2014) with β_K = 1 and DCD as the local
solver — the synchronized parallel-DCD competitor from the paper's §5.

Outer round: every partition k runs H local DCD updates starting from the
*shared* w snapshot, accumulating a local primal delta Δw_k while only
touching its own dual block; the driver then merges

    w ← w + (β_K / K) Σ_k Δw_k ,   α_k ← α_k + (β_K / K) Δα_k ,

with the safe averaging choice β_K = 1.  Partitions are simulated with
``vmap`` (deterministic; semantics identical to K synchronized workers).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objective import duality_gap, f32_dot, w_of_alpha


class CocoaResult(NamedTuple):
    alpha: jnp.ndarray
    w: jnp.ndarray
    gaps: jnp.ndarray
    rounds: int


class CocoaPodResult(NamedTuple):
    """Result of ``cocoa_pod_solve`` — ``gaps``/``eps`` are aligned with
    the pod solver's record schedule (every ``gap_every`` epochs plus
    the final one); ``eps`` is the backward error ‖w(α) − ŵ‖ against
    the (possibly stale) merged read view ŵ."""

    alpha: jnp.ndarray
    w: jnp.ndarray
    gaps: jnp.ndarray
    eps: jnp.ndarray
    rounds: int
    # segmented-replay carry (``flush=False`` only): the live FIFO and
    # PRNG key to hand the next segment (None on a flushed whole solve)
    fifo: tuple | None = None
    key: jnp.ndarray | None = None


@functools.partial(jax.jit, static_argnames=("loss", "n_partitions", "local_steps"))
def _cocoa_round(X, sq_norms, alpha, w, part_idx, perm_keys, loss,
                 n_partitions, local_steps):
    """part_idx: (K, n_k) fixed row partition; perm_keys: (K,) PRNG keys."""

    def local_solve(rows_idx, key):
        local_perm = jax.random.permutation(key, rows_idx.shape[0])

        def body(t, carry):
            d_alpha, w_loc = carry
            i = rows_idx[local_perm[t % rows_idx.shape[0]]]
            x = X[i]
            a_i = alpha[i] + d_alpha[local_perm[t % rows_idx.shape[0]]]
            delta = loss.delta(a_i, f32_dot(w_loc, x), sq_norms[i])
            d_alpha = d_alpha.at[local_perm[t % rows_idx.shape[0]]].add(delta)
            return d_alpha, w_loc + delta * x

        d_alpha0 = jnp.zeros((rows_idx.shape[0],), alpha.dtype)
        d_alpha, w_loc = jax.lax.fori_loop(0, local_steps, body, (d_alpha0, w))
        return d_alpha, w_loc - w  # (Δα_k, Δw_k)

    d_alphas, d_ws = jax.vmap(local_solve)(part_idx, perm_keys)  # (K,n_k),(K,d)
    scale = 1.0 / n_partitions  # β_K = 1
    w = w + scale * jnp.sum(d_ws, axis=0)
    alpha = alpha.at[part_idx.reshape(-1)].add(scale * d_alphas.reshape(-1))
    return alpha, w


def cocoa_solve(
    X,
    loss,
    *,
    n_partitions: int = 4,
    outer_rounds: int = 20,
    local_steps: int | None = None,
    seed: int = 0,
    record: bool = True,
) -> CocoaResult:
    n, d = X.shape
    n_k = n // n_partitions
    sq_norms = jnp.sum(X * X, axis=1)
    key = jax.random.PRNGKey(seed)
    key, kpart = jax.random.split(key)
    part_idx = jax.random.permutation(kpart, n)[: n_k * n_partitions].reshape(
        n_partitions, n_k
    )
    if local_steps is None:
        local_steps = n_k  # one local epoch per outer round
    alpha = jnp.zeros((n,), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    gaps = []
    for _ in range(outer_rounds):
        key, sub = jax.random.split(key)
        perm_keys = jax.random.split(sub, n_partitions)
        alpha, w = _cocoa_round(
            X, sq_norms, alpha, w, part_idx, perm_keys, loss,
            n_partitions, local_steps,
        )
        if record:
            gaps.append(float(duality_gap(alpha, X, loss)))
    # w tracked by CoCoA equals w(α) exactly (updates are lossless).
    return CocoaResult(alpha, w_of_alpha(X, alpha), jnp.asarray(gaps), outer_rounds)


@functools.partial(jax.jit, static_argnames=("loss",))
def _pod_local_epoch(X, sq_norms, alpha, w, base, nvalid, rows, loss):
    """One pod's serial local epoch from the shared (α, w) snapshot:
    the drawn local-row sequence ``rows`` (already masked to the valid
    prefix and cycled over the tail, exactly like the device draw)
    updated with locally-fresh w.  ``base`` is the pod's first global
    row id, ``nvalid`` its real row count — a drawn slot past it (only
    possible for a pod owning nothing but padding) takes an exact
    zero-delta update, matching the solver's q←1 zero-row convention.
    Returns (Δα on the full dual vector, Δw)."""
    n = X.shape[0]

    def body(t, carry):
        a, w_loc = carry
        ok = rows[t] < nvalid
        i = jnp.minimum(base + rows[t], n - 1)
        x = X[i]
        delta = loss.delta(a[i], f32_dot(w_loc, x), sq_norms[i])
        delta = jnp.where(ok, delta, 0.0)
        return a.at[i].add(delta), w_loc + delta * x

    a1, w1 = jax.lax.fori_loop(0, rows.shape[0], body, (alpha, w))
    return a1 - alpha, w1 - w


def cocoa_pod_solve(
    X,
    loss,
    *,
    n_pods: int = 2,
    epochs: int = 10,
    block_size: int = 64,
    pod_delay_rounds: int = 0,
    seed: int = 0,
    record: bool = True,
    gap_every: int = 1,
    alpha0=None,
    w0=None,
    epoch_start: int = 0,
    total_epochs: int | None = None,
    key0=None,
    fifo0=None,
    flush: bool = True,
) -> CocoaPodResult:
    """Serial host-loop oracle for the double-async pod solver
    (DESIGN.md §13) — ``sharded_passcode_solve`` on a ``(pod=n_pods,
    data=1)`` mesh replayed as plain Python: per epoch each pod runs
    one serial local epoch (locally-fresh w) on its contiguous row
    shard from the shared (α, w) snapshot, then α picks up 1/K of its
    own pod's Δα and w picks up the pod-mean Δw through a
    ``pod_delay_rounds``-deep FIFO (flushed after the last epoch).

    The PRNG chain, the per-pod block draw
    (``repro.core.sharded._device_block_perm_v`` with fleet index k of
    n_pods keys) and the record schedule are the SPMD solver's own, so
    at ``data=1`` the trajectories agree to float tolerance — the
    equivalence spine of ``tests/test_sharded_pod.py``.
    ``pod_delay_rounds=0`` with ``n_pods=K`` is a synchronous CoCoA
    outer round over contiguous partitions.  Dense math throughout (an
    ``EllMatrix`` input is densified): this is the trustworthy-but-slow
    reference, not a fast path.

    Segmented replay (the oracle side of ``repro.resilience``,
    DESIGN.md §14): ``epoch_start``/``total_epochs`` run a slice
    [epoch_start, epoch_start + epochs) of a ``total_epochs`` solve —
    the record schedule keys on the *global* epoch, and the PRNG chain
    fast-forwards ``epoch_start`` splits when no explicit ``key0`` is
    handed in.  ``flush=False`` returns the live FIFO and key in the
    result instead of flushing, so the next segment (fed ``alpha0``/
    ``w0``/``fifo0``/``key0`` from this one) continues bit-identically
    — chaining segments reproduces the whole solve exactly, which is
    how a rollback replay is checked against the oracle."""
    from repro.core.sharded import _device_block_perm_v, _n_blocks

    Xd = X.to_dense() if hasattr(X, "to_dense") else jnp.asarray(X)
    n, d = Xd.shape
    P = int(n_pods)
    if P < 1:
        raise ValueError(f"n_pods must be >= 1, got {P}")
    delay = int(pod_delay_rounds)
    if delay < 0:
        raise ValueError(f"pod_delay_rounds must be >= 0, got {delay}")
    n_pod_loc = max(-(-n // P), 1)
    n_blocks = _n_blocks(n_pod_loc, block_size)
    sq_norms = jnp.sum(Xd * Xd, axis=1)
    scale = 1.0 / P
    gap_every = max(int(gap_every), 1)
    e0 = int(epoch_start)
    total = int(total_epochs) if total_epochs is not None else e0 + epochs
    alpha = (jnp.zeros((n,), jnp.float32) if alpha0 is None
             else jnp.asarray(alpha0, jnp.float32))
    w = (jnp.zeros((d,), jnp.float32) if w0 is None
         else jnp.asarray(w0, jnp.float32))
    if fifo0 is not None:
        fifo = [jnp.asarray(g, jnp.float32) for g in fifo0]
        if len(fifo) != delay:
            raise ValueError(
                f"fifo0 has depth {len(fifo)}, expected {delay}")
    else:
        fifo = [jnp.zeros((d,), jnp.float32) for _ in range(delay)]
    if key0 is not None:
        key = jnp.asarray(key0)
    else:
        key = jax.random.PRNGKey(seed)
        for _ in range(e0):  # fast-forward the chain to epoch_start
            key, _ = jax.random.split(key)
    gaps, eps = [], []
    for e in range(e0, e0 + epochs):
        key, sub = jax.random.split(key)
        d_alpha = jnp.zeros_like(alpha)
        g = jnp.zeros_like(w)
        for kp in range(P):
            v = min(max(n - kp * n_pod_loc, 1), n_pod_loc)
            rows = _device_block_perm_v(sub, kp, P, n_pod_loc, v,
                                        n_blocks,
                                        block_size).reshape(-1)
            da, dw = _pod_local_epoch(Xd, sq_norms, alpha, w,
                                      kp * n_pod_loc,
                                      max(n - kp * n_pod_loc, 0),
                                      rows, loss)
            d_alpha = d_alpha + da
            g = g + dw
        alpha = alpha + scale * d_alpha
        g = scale * g
        if delay == 0:
            w = w + g
        else:
            w = w + fifo.pop(0)
            fifo.append(g)
        if record and ((e + 1) % gap_every == 0 or e == total - 1):
            gaps.append(float(duality_gap(alpha, Xd, loss)))
            eps.append(float(jnp.linalg.norm(w_of_alpha(Xd, alpha) - w)))
    if not flush:
        return CocoaPodResult(alpha, w, jnp.asarray(gaps, jnp.float32),
                              jnp.asarray(eps, jnp.float32), epochs,
                              fifo=tuple(fifo), key=key)
    for g_in in fifo:
        w = w + g_in  # flush the in-flight merges
    return CocoaPodResult(alpha, w, jnp.asarray(gaps, jnp.float32),
                          jnp.asarray(eps, jnp.float32), epochs)
