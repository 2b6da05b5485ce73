"""Ragged problem data for the benchmark: rows of heavy-tailed length,
drawn from ``--seed``.

The law of ``bench/gen.py`` (Zipf-weighted distinct ids, N(0, 1) values
scaled to unit norm, labels from a sparse ground truth with flips),
except that row i holds L_i ids, with the lengths drawn on the host:

* L_i = exp(μ + σ·z_i), z_i ~ N(0, 1), μ = ln(mean) − σ²/2 (so that the
  law's mean is the configuration's ``nnz_mean``);
* rounded, clipped to ``length_clip`` and scaled by one factor, found by
  bisection, so that the realised mean lies within
  ``length_mean_tolerance`` of ``nnz_mean``.

The ids, values and labels are drawn on the device, rows grouped by
width class (16·2^b slots, b = 0..8): each class runs ``gen.py``'s
distinct-id draw at its width, in chunks of a fixed number of rows (one
compile per class, whatever the seed), and a row keeps the first L_i of
its class's distinct ids, which are the first L_i distinct ids of the
same i.i.d. draw.  The rows land in one compressed (CSR) pair of arrays
on the device.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from bench import gen

WIDTHS = tuple(16 << b for b in range(9))  # 16 .. 4096 slots


class RaggedSplit(NamedTuple):
    """One CSR split: (nnz,) ids and label-folded values on the device,
    (n + 1,) host row offsets."""

    indices: jax.Array
    values: jax.Array
    indptr: np.ndarray
    d: int


def row_lengths(cfg: dict, seed: int, n: int, salt: int = 1) -> np.ndarray:
    """The n row lengths of a split (host, int64), by the law above."""
    law = cfg["assumed"]
    mean, sigma = float(cfg["nnz_mean"]), float(law["length_sigma"])
    lo, hi = (int(v) for v in law["length_clip"])
    tol = float(law["length_mean_tolerance"])
    rng = np.random.default_rng(gen.seed_words(seed, 3, salt))
    raw = np.exp(np.log(mean) - sigma**2 / 2 + sigma * rng.standard_normal(n))

    def lengths(scale):
        return np.clip(np.rint(scale * raw), lo, hi).astype(np.int64)

    a, b = 0.25, 4.0  # the realised mean rises with the scale
    for _ in range(60):
        mid = (a + b) / 2
        if lengths(mid).mean() < mean:
            a = mid
        else:
            b = mid
    out = min((lengths(s) for s in (a, b)),
              key=lambda x: abs(x.mean() - mean))
    if abs(out.mean() - mean) > tol:
        raise ValueError(f"no scale brings the mean length to {mean} "
                         f"+- {tol} (got {out.mean():.3f})")
    return out


def _chunk_rows(width: int) -> int:
    """Rows per draw call at a width: about 2^23 ids drawn per call."""
    return max(gen.CHUNK_ROWS >> max(WIDTHS.index(width) - 3, 0), 1)


@functools.partial(jax.jit, static_argnames=("width", "margin",
                                             "label_noise", "n_iter"),
                   donate_argnums=(0, 1))
def _fill(flat_ids, flat_vals, key, w_true, tables, start, lens, *,
          width: int, margin: float, label_noise: float, n_iter: int):
    """Draw one chunk of rows of a width class and write each row's
    first ``lens`` entries at ``start`` in the flat arrays (a row with
    lens 0 writes nothing)."""
    rows = start.shape[0]
    k_ids, k_val, k_mar, k_flip = jax.random.split(key, 4)
    ids = gen._chunk_ids(k_ids, tables, rows, width, n_iter)
    live = jnp.arange(width)[None, :] < lens[:, None]
    val = jnp.where(live, jax.random.normal(k_val, (rows, width)), 0.0)
    norms = jnp.sqrt(jnp.sum(val * val, axis=1, keepdims=True))
    val = val / jnp.maximum(norms, 1e-8)
    m = jnp.sum(val * w_true[ids], axis=1)
    noise = jax.random.normal(k_mar, (rows,), jnp.float32)
    y = jnp.where(m + margin * noise > 0, 1.0, -1.0)
    flip = jax.random.uniform(k_flip, (rows,)) < label_noise
    y = jnp.where(flip, -y, y)
    dest = jnp.where(live, start[:, None] + jnp.arange(width)[None, :],
                     flat_ids.shape[0])  # past the end: dropped
    return (flat_ids.at[dest].set(ids, mode="drop"),
            flat_vals.at[dest].set(val * y[:, None], mode="drop"))


def make_split(cfg: dict, seed: int, *, salt: int = 1,
               n: int | None = None) -> RaggedSplit:
    """A split of ``n`` rows (default ``n_train``) from ``seed``: lengths
    on the host, everything else on the device."""
    d = int(cfg["d"])
    n = int(cfg["n_train"] if n is None else n)
    law = cfg["assumed"]
    lens = row_lengths(cfg, seed, n, salt)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    nnz = int(indptr[-1])
    # the flat arrays' length is the law's bound on nnz, the same for
    # every seed, so that the draw compiles once per width class
    cap = int(n * (float(cfg["nnz_mean"])
                   + float(law["length_mean_tolerance"])))
    tables, n_iter = gen.zipf_tables(d, law["zipf_exponent"])
    tables = tuple(jnp.asarray(a) for a in tables)
    w_true = gen._w_true(gen.key_from_seed(seed, 0), d)
    key = gen.key_from_seed(seed, salt)
    flat_ids = jnp.zeros((cap,), jnp.int32)
    flat_vals = jnp.zeros((cap,), jnp.float32)
    cls = np.searchsorted(np.asarray(WIDTHS), lens)  # smallest width ≥ L
    if 2 * WIDTHS[int(cls.max())] > d:
        raise ValueError(f"rows of up to {int(lens.max())} distinct ids "
                         f"need d well above {WIDTHS[int(cls.max())]}; "
                         f"d is {d}")
    for b, width in enumerate(WIDTHS):
        rows = np.flatnonzero(cls == b)
        r = _chunk_rows(width)
        for c in range(-(-rows.size // r)):
            part = rows[c * r:(c + 1) * r]
            start = np.zeros(r, np.int32)
            take = np.zeros(r, np.int32)
            start[:part.size] = indptr[part]
            take[:part.size] = lens[part]
            flat_ids, flat_vals = _fill(
                flat_ids, flat_vals,
                jax.random.fold_in(jax.random.fold_in(key, b), c), w_true,
                tables, jnp.asarray(start), jnp.asarray(take), width=width,
                margin=float(law["margin"]),
                label_noise=float(law["label_noise"]), n_iter=n_iter)
    return RaggedSplit(flat_ids[:nnz], flat_vals[:nnz], indptr, d)
