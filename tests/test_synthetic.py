"""repro.data.synthetic: the vectorised row generator keeps the recipe's
distribution (Zipf-popular, distinct ids per row, unit-norm rows)."""

import numpy as np
import pytest

from repro.data.synthetic import (
    DatasetRecipe,
    _first_distinct,
    _zipf_probs,
    _zipf_rows,
    make_dataset,
)


def test_first_distinct_keeps_draw_order():
    draws = np.array([[5, 5, 2, 7, 2, 9],
                      [1, 1, 1, 1, 3, 1]], np.int32)
    out, ok = _first_distinct(draws, 3)
    assert ok.tolist() == [True, False]
    assert out[0].tolist() == [5, 2, 7]


def test_zipf_rows_match_sampling_without_replacement():
    rng = np.random.default_rng(0)
    d, k, n = 50, 10, 20_000
    rows = _zipf_rows(rng, n, d, k)
    assert rows.shape == (n, k)
    assert all(len(set(r)) == k for r in rows)
    p = _zipf_probs(d)
    ref = np.stack([rng.choice(d, k, replace=False, p=p)
                    for _ in range(n)])
    f = np.bincount(rows.ravel(), minlength=d) / rows.size
    f_ref = np.bincount(ref.ravel(), minlength=d) / ref.size
    assert np.abs(f - f_ref).max() < 0.005


@pytest.mark.parametrize("d,k", [(100, 90), (128, 127)])
def test_zipf_rows_k_close_to_d(d, k):
    # the tail ids seldom all come up in an over-draw: the short rows
    # fall back to per-row sampling without replacement, and end
    ds = make_dataset("near-dense", seed=0,
                      recipe=DatasetRecipe("near-dense", 200, 20, d, k, 1.0))
    for X in (ds.X_train, ds.X_test):
        idx = np.asarray(X.indices)
        assert idx.shape[1] == k
        assert all(len(set(r)) == k for r in idx)
        assert idx.min() >= 0 and idx.max() < d


@pytest.mark.parametrize("name", ["tiny", "rcv1"])
def test_rows_are_distinct_unit_norm(name):
    ds = make_dataset(name, seed=3)
    for X in (ds.X_train, ds.X_test):
        idx = np.asarray(X.indices)
        val = np.asarray(X.values)
        assert all(len(set(r)) == idx.shape[1] for r in idx)
        np.testing.assert_allclose(np.linalg.norm(val, axis=1), 1.0,
                                   rtol=1e-5)
        assert idx.min() >= 0 and idx.max() < X.n_features


def test_seeded_and_deterministic():
    r = DatasetRecipe("mini", 300, 50, 1000, 20, 1.0)
    a = make_dataset("mini", seed=7, recipe=r)
    b = make_dataset("mini", seed=7, recipe=r)
    c = make_dataset("mini", seed=8, recipe=r)
    np.testing.assert_array_equal(np.asarray(a.X_train.indices),
                                  np.asarray(b.X_train.indices))
    np.testing.assert_array_equal(np.asarray(a.X_test.values),
                                  np.asarray(b.X_test.values))
    assert not np.array_equal(np.asarray(a.X_train.indices),
                              np.asarray(c.X_train.indices))
