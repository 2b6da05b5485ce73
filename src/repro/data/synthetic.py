"""Synthetic linear-classification datasets matched to the paper's Table 3.

The container is offline, so rcv1/news20/covtype/webspam/kddb cannot be
downloaded.  We generate datasets that preserve the *structural*
statistics that matter for DCD behaviour — (n, d, avg nnz/row, C,
density regime, separability) — at reduced scale, and benchmark on those.
Rows are L2-normalized to ≤ 1 (matching the paper's R_max = 1 assumption
and standard LIBLINEAR preprocessing) and label-folded (x_i = y_i·ẋ_i).

Recipes (scaled ~1/40 each axis to fit a 1-core CPU CI budget):

    name          n       d      nnz/row   C       mirrors
    news20-like   2,000   8,192  60        2.0     n ≪ d, sparse, separable
    covtype-like  8,000   54     12 (dense)0.0625  n ≫ d, dense rows
    rcv1-like     8,000   4,096  73        1.0     sparse, mid
    webspam-like  4,000   8,192  200       1.0     denser sparse rows
    kddb-like     16,000  16,384 30        1.0     n & d both large, very sparse
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax.numpy as jnp

from repro.data.sparse import EllMatrix


@dataclasses.dataclass(frozen=True)
class DatasetRecipe:
    name: str
    n_train: int
    n_test: int
    d: int
    nnz_per_row: int  # == d → dense
    C: float
    label_noise: float = 0.02
    margin: float = 0.5


DATASET_RECIPES = {
    "news20": DatasetRecipe("news20", 2_000, 500, 8_192, 60, 2.0),
    "covtype": DatasetRecipe("covtype", 8_000, 1_000, 54, 54, 0.0625,
                             label_noise=0.15, margin=0.1),
    "rcv1": DatasetRecipe("rcv1", 8_000, 1_000, 4_096, 73, 1.0),
    "webspam": DatasetRecipe("webspam", 4_000, 1_000, 8_192, 200, 1.0),
    "kddb": DatasetRecipe("kddb", 16_000, 2_000, 16_384, 30, 1.0,
                          label_noise=0.05),
    # tiny recipes for unit tests
    "tiny": DatasetRecipe("tiny", 256, 64, 128, 16, 1.0),
    "tiny-dense": DatasetRecipe("tiny-dense", 256, 64, 32, 32, 1.0),
}


@dataclasses.dataclass
class SyntheticDataset:
    recipe: DatasetRecipe
    X_train: EllMatrix  # label-folded rows
    X_test: EllMatrix
    w_true: np.ndarray

    def dense_train(self) -> jnp.ndarray:
        return self.X_train.to_dense()

    def dense_test(self) -> jnp.ndarray:
        return self.X_test.to_dense()


def _zipf_probs(d: int) -> np.ndarray:
    p = 1.0 / np.arange(1, d + 1) ** 0.9  # bag-of-words-ish popularity
    return p / p.sum()


_CHUNK_ROWS = 1 << 16
_MAX_REDRAWS = 4


def _first_distinct(draws: np.ndarray, k: int):
    """Per row, the first ``k`` distinct ids of ``draws`` in draw order,
    and which rows had at least ``k`` distinct ids."""
    order = np.argsort(draws, axis=1, kind="stable")
    srt = np.take_along_axis(draws, order, axis=1)
    first_sorted = np.ones(draws.shape, bool)
    first_sorted[:, 1:] = srt[:, 1:] != srt[:, :-1]
    first = np.empty_like(first_sorted)
    np.put_along_axis(first, order, first_sorted, axis=1)
    keep = first & (np.cumsum(first, axis=1) <= k)
    ok = keep.sum(axis=1) == k
    out = np.zeros((draws.shape[0], k), np.int32)
    out[ok] = draws[ok][keep[ok]].reshape(-1, k)
    return out, ok


def _zipf_rows(rng, n: int, d: int, k: int) -> np.ndarray:
    """(n, k) column ids: each row k DISTINCT ids drawn Zipf-weighted.

    The first k distinct ids of an i.i.d. Zipf stream are a draw of k
    ids without replacement (successive sampling), so an over-draw with
    replacement, deduplicated per row, gives the without-replacement
    distribution with no per-row Python loop; the rare row with fewer
    than k distinct ids in its over-draw is redrawn, up to
    ``_MAX_REDRAWS`` times.  Rows still short after that (k close to d,
    where the tail ids seldom all come up) are drawn one by one without
    replacement."""
    p = _zipf_probs(d)
    cdf = np.cumsum(p)
    idx = np.empty((n, k), np.int32)
    over = 2 * k + 8
    for lo in range(0, n, _CHUNK_ROWS):
        todo = np.arange(lo, min(lo + _CHUNK_ROWS, n))
        for _ in range(_MAX_REDRAWS):
            if not todo.size:
                break
            u = rng.random((todo.size, over)) * cdf[-1]
            draws = np.minimum(np.searchsorted(cdf, u, side="right"),
                               d - 1).astype(np.int32)
            rows, ok = _first_distinct(draws, k)
            idx[todo[ok]] = rows[ok]
            todo = todo[~ok]
        for i in todo:
            idx[i] = rng.choice(d, k, replace=False, p=p)
    return idx


def _draw_split(rng, recipe: DatasetRecipe, n: int, w_true: np.ndarray):
    """n label-folded unit-norm rows with labels from ``w_true``."""
    d, k = recipe.d, recipe.nnz_per_row
    if k >= d:
        idx = np.tile(np.arange(d, dtype=np.int32), (n, 1))
        val = rng.standard_normal((n, d)).astype(np.float32)
    else:
        # zipf-weighted ids WITHOUT replacement within a row: popularity
        # skew and no duplicate column ids (duplicates would make ELL row
        # norms disagree with the densified matrix)
        idx = _zipf_rows(rng, n, d, k)
        val = rng.standard_normal((n, k)).astype(np.float32)
    # normalize rows to unit norm (R_max = 1)
    norms = np.sqrt((val**2).sum(axis=1, keepdims=True))
    val = val / np.maximum(norms, 1e-8)
    # margins and labels
    margins = (val * w_true[idx]).sum(axis=1)
    y = np.where(margins + recipe.margin * rng.standard_normal(n) > 0,
                 1.0, -1.0)
    flip = rng.random(n) < recipe.label_noise
    y = np.where(flip, -y, y).astype(np.float32)
    val = val * y[:, None]  # label folding: x_i = y_i * raw_i
    return EllMatrix(jnp.asarray(idx), jnp.asarray(val), d)


def make_dataset(name: str, seed: int = 0,
                 recipe: Optional[DatasetRecipe] = None) -> SyntheticDataset:
    recipe = recipe or DATASET_RECIPES[name]
    rng = np.random.default_rng(seed)
    w_true = rng.standard_normal(recipe.d).astype(np.float32)
    w_true *= (np.abs(w_true) > 0.6)  # sparse-ish ground truth
    X_train = _draw_split(rng, recipe, recipe.n_train, w_true)
    # the test split shares w_true, drawn from its own stream
    X_test = _draw_split(np.random.default_rng(seed + 1), recipe,
                         recipe.n_test, w_true)
    return SyntheticDataset(recipe, X_train, X_test, w_true)
