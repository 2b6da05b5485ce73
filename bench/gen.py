"""Problem data for the benchmark, drawn on the device from ``--seed``.

The benchmark's own generator, so that a change to the program's
``repro.data.synthetic`` cannot move the yardstick.  It draws from the
same law as that generator's ``_draw_split``:

* a ground truth ``w_true`` ~ N(0, 1)^d, zeroed where |w| <= 0.6;
* per row, k DISTINCT column ids drawn Zipf-weighted (p_j ∝ j^-s) without
  replacement: the first k distinct ids of an i.i.d. over-draw of
  ``2k + 8`` ids, the row redrawn while its over-draw holds fewer than k
  distinct ids (successive sampling);
* values N(0, 1), each row scaled to unit norm;
* label y = sign(w_trueᵀx + margin·N(0, 1)), flipped with probability
  ``label_noise``, folded into the row (x_i = y_i·ẋ_i).

The i.i.d. Zipf draws are exact: a 64-bit uniform is compared against the
cumulative weights as 64-bit integers (two uint32 words), by a binary
search on the device.  The search starts from a table, indexed by the
uniform's top ``BUCKET_BITS`` bits, of the first and last id a draw in
that bucket can land on, so that it halves a range of a few ids and not
all d (each halving is a gather, and gathers are what a draw costs on a
TPU).  Everything runs in one jitted call per split, in row chunks, so
set-up pays no host loop and no host-to-device copy of the data.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

CHUNK_ROWS = 1 << 15
BUCKET_BITS = 20


class Split(NamedTuple):
    """One ELL split on the device: (n, k) ids and label-folded values."""

    indices: jax.Array
    values: jax.Array
    d: int


def seed_words(seed: int, *salt: int) -> tuple:
    """Two uint32 words from a seed of any size (``--seed`` may exceed
    32 bits, which ``jax.random.PRNGKey`` would silently truncate)."""
    st = np.random.SeedSequence([int(seed) & (2**64 - 1), *salt])
    a, b = st.generate_state(2, np.uint32)
    return int(a), int(b)


def key_from_seed(seed: int, *salt: int) -> jax.Array:
    a, b = seed_words(seed, *salt)
    return jax.random.wrap_key_data(jnp.array([a, b], jnp.uint32),
                                    impl="threefry2x32")


def _cdf_u64(d: int, exponent: float) -> np.ndarray:
    """Cumulative Zipf weights (p_j ∝ j^-s) as 64-bit integers: id j is
    drawn when cdf[j-1] <= u < cdf[j] for a uniform u in [0, 2^64).  The
    last entry saturates at 2^64 - 1."""
    p = 1.0 / np.arange(1, d + 1, dtype=np.float64) ** float(exponent)
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    scaled = np.ldexp(cdf, 64)
    top = np.float64(2.0**64)
    return np.where(scaled >= top, np.uint64(2**64 - 1),
                    np.minimum(scaled, np.nextafter(top, 0))
                    .astype(np.uint64))


def zipf_tables(d: int, exponent: float):
    """The search tables of the Zipf draws, as numpy arrays: the
    cumulative weights split into (hi, lo) uint32 words; per bucket of the
    uniform's top ``BUCKET_BITS`` bits, the first and the last id a draw
    in it can land on (the draw is monotone in u); and the halvings the
    widest such range needs."""
    as_int = _cdf_u64(d, exponent)
    hi = (as_int >> np.uint64(32)).astype(np.uint32)
    lo = (as_int & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    step = np.uint64(1) << np.uint64(64 - BUCKET_BITS)
    starts = np.arange(2**BUCKET_BITS, dtype=np.uint64) * step
    first = np.searchsorted(as_int, starts, side="right")
    last = np.searchsorted(as_int, starts + (step - np.uint64(1)),
                           side="right")
    first = np.minimum(first, d - 1).astype(np.int32)
    last = np.minimum(last, d - 1).astype(np.int32)
    n_iter = int(np.ceil(np.log2(int((last - first).max()) + 1))) + 1
    return (hi, lo, first, last), n_iter


def _zipf_draws(key, tables, shape, n_iter: int):
    """i.i.d. Zipf ids of ``shape``: per draw, the first id whose
    cumulative weight exceeds a 64-bit uniform, by a binary search inside
    the uniform's bucket."""
    cdf_hi, cdf_lo, b_first, b_last = tables
    kh, kl = jax.random.split(key)
    u_hi = jax.random.bits(kh, shape, jnp.uint32)
    u_lo = jax.random.bits(kl, shape, jnp.uint32)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        c_hi = cdf_hi[mid]
        c_lo = cdf_lo[mid]
        le = (c_hi < u_hi) | ((c_hi == u_hi) & (c_lo <= u_lo))
        active = lo < hi
        lo = jnp.where(active & le, mid + 1, lo)
        hi = jnp.where(active & ~le, mid, hi)
        return lo, hi

    bucket = (u_hi >> (32 - BUCKET_BITS)).astype(jnp.int32)
    lo, _ = jax.lax.fori_loop(0, n_iter, step,
                              (b_first[bucket], b_last[bucket]))
    return lo


def first_distinct(draws, k: int):
    """Per row, the first ``k`` distinct ids of ``draws`` in draw order,
    and whether the row had at least ``k`` distinct ids."""
    n, over = draws.shape
    pos = jnp.broadcast_to(jnp.arange(over, dtype=jnp.int32), (n, over))
    srt, spos = jax.lax.sort((draws, pos), dimension=1, num_keys=1,
                             is_stable=True)
    first_s = jnp.concatenate(
        [jnp.ones((n, 1), bool), srt[:, 1:] != srt[:, :-1]], axis=1)
    # back to draw order: sort the flags by their original position
    _, first = jax.lax.sort((spos, first_s), dimension=1, num_keys=1)
    keep = first & (jnp.cumsum(first, axis=1) <= k)
    ok = jnp.sum(keep, axis=1) == k
    order_key = jnp.where(keep, pos, over + pos)
    _, picked = jax.lax.sort((order_key, draws), dimension=1, num_keys=1)
    return picked[:, :k], ok


def _chunk_ids(key, tables, rows: int, k: int, n_iter: int):
    over = 2 * k + 8

    def draw(i):
        return first_distinct(
            _zipf_draws(jax.random.fold_in(key, i), tables, (rows, over),
                        n_iter), k)

    ids, ok = draw(0)

    def cond(c):
        return ~jnp.all(c[1])

    def body(c):
        ids, ok, i = c
        ids2, ok2 = draw(i)
        take = ~ok & ok2
        return (jnp.where(take[:, None], ids2, ids), ok | ok2, i + 1)

    ids, _, _ = jax.lax.while_loop(cond, body, (ids, ok, jnp.int32(1)))
    return ids


@functools.partial(jax.jit, static_argnames=("n", "d", "k", "margin",
                                             "label_noise", "n_iter"))
def _split(key, w_true, tables, *, n: int, d: int, k: int, margin: float,
           label_noise: float, n_iter: int):
    rows = min(CHUNK_ROWS, n)
    n_chunks = -(-n // rows)

    def one(c):
        kc = jax.random.fold_in(key, c)
        k_ids, k_val, k_mar, k_flip = jax.random.split(kc, 4)
        if k >= d:
            ids = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32),
                                   (rows, d))
        else:
            ids = _chunk_ids(k_ids, tables, rows, k, n_iter)
        val = jax.random.normal(k_val, (rows, k), jnp.float32)
        norms = jnp.sqrt(jnp.sum(val * val, axis=1, keepdims=True))
        val = val / jnp.maximum(norms, 1e-8)
        m = jnp.sum(val * w_true[ids], axis=1)
        noise = jax.random.normal(k_mar, (rows,), jnp.float32)
        y = jnp.where(m + margin * noise > 0, 1.0, -1.0)
        flip = jax.random.uniform(k_flip, (rows,)) < label_noise
        y = jnp.where(flip, -y, y)
        return ids, val * y[:, None]

    ids, val = jax.lax.map(one, jnp.arange(n_chunks, dtype=jnp.int32))
    return (ids.reshape(n_chunks * rows, k)[:n],
            val.reshape(n_chunks * rows, k)[:n])


def make_split(key, w_true, tables, *, n: int, d: int, k: int,
               margin: float, label_noise: float, n_iter: int) -> Split:
    """n label-folded unit-norm rows on the device (one jitted call)."""
    ids, val = _split(key, w_true, tables, n=n, d=d, k=k,
                      margin=float(margin), label_noise=float(label_noise),
                      n_iter=n_iter)
    return Split(ids, val, d)


@functools.partial(jax.jit, static_argnums=1)
def _w_true(key, d: int):
    w = jax.random.normal(key, (d,), jnp.float32)
    return w * (jnp.abs(w) > 0.6)


def make_problem(cfg: dict, seed: int, *, train: bool = True,
                 test: bool = True):
    """(train, test) splits of a configuration from ``seed``; a split not
    asked for is None.  Both share one ``w_true``; each split has its
    own stream, so asking for one does not change the other."""
    d, k = int(cfg["d"]), int(cfg["nnz_per_row"])
    law = cfg["assumed"]
    tables, n_iter = zipf_tables(d, law["zipf_exponent"])
    tables = tuple(jnp.asarray(a) for a in tables)
    w_true = _w_true(key_from_seed(seed, 0), d)
    kw = dict(d=d, k=k, margin=law["margin"],
              label_noise=law["label_noise"], n_iter=n_iter)
    out = []
    for salt, want, n in ((1, train, cfg["n_train"]),
                          (2, test, cfg["n_test"])):
        out.append(make_split(key_from_seed(seed, salt), w_true, tables,
                              n=int(n), **kw) if want else None)
    return tuple(out)
