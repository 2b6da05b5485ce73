"""The ragged generator draws the law its configuration states, from the
seed alone, and the configuration cannot be read as a fixed-width one."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import gen, gen_ragged, harness

TAIL = harness.load_json(os.path.join(harness.BENCH, "configs",
                                      "rcv1-tail.json"))
SMALL = dict(TAIL, n_train=3000, d=2000, nnz_mean=14.0,
             assumed=dict(TAIL["assumed"], length_clip=[2, 60]))


@pytest.mark.parametrize("seed", [1, 2**33 + 5, 4000000001])
def test_length_law_at_full_size(seed):
    lens = gen_ragged.row_lengths(TAIL, seed, TAIL["n_train"])
    law = TAIL["assumed"]
    lo, hi = law["length_clip"]
    assert abs(lens.mean() - TAIL["nnz_mean"]) <= law["length_mean_tolerance"]
    assert lens.min() >= lo and lens.max() <= hi
    assert 48 <= np.median(lens) <= 58  # log-normal, sigma 0.8: about 53
    assert lens.max() > 20 * np.median(lens)  # the heavy tail is there
    wid = -(-lens // 16) * 16
    assert 1 - lens.sum() / wid.sum() < 0.2  # what the packed walk pads


def test_lengths_come_from_the_seed():
    a = gen_ragged.row_lengths(TAIL, 2**40 + 3, 5000)
    b = gen_ragged.row_lengths(TAIL, 2**40 + 3, 5000)
    c = gen_ragged.row_lengths(TAIL, 2**40 + 4, 5000)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_config_is_not_a_fixed_width_one():
    assert "nnz_per_row" not in TAIL
    with pytest.raises(KeyError):
        gen.make_problem(TAIL, 1, test=False)


def test_split_is_csr_of_distinct_unit_rows_from_the_seed():
    s = gen_ragged.make_split(SMALL, 2**33 + 9)
    lens = np.diff(s.indptr)
    np.testing.assert_array_equal(
        lens, gen_ragged.row_lengths(SMALL, 2**33 + 9, SMALL["n_train"]))
    ids, vals = np.asarray(s.indices), np.asarray(s.values)
    assert ids.size == vals.size == s.indptr[-1]
    assert ids.min() >= 0 and ids.max() < SMALL["d"]
    rows = np.repeat(np.arange(lens.size), lens)
    norms = np.bincount(rows, weights=vals.astype(np.float64) ** 2)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    for i in range(0, lens.size, 97):
        r = ids[s.indptr[i]:s.indptr[i + 1]]
        assert np.unique(r).size == r.size
    again = gen_ragged.make_split(SMALL, 2**33 + 9)
    np.testing.assert_array_equal(np.asarray(again.indices), ids)
    np.testing.assert_array_equal(np.asarray(again.values), vals)
    other = gen_ragged.make_split(SMALL, 2**33 + 10)
    assert not np.array_equal(np.asarray(other.values)[:100], vals[:100])


def test_rows_too_long_for_d_are_refused():
    bad = dict(SMALL, d=100)
    with pytest.raises(ValueError, match="need d"):
        gen_ragged.make_split(bad, 3)


def test_benchmark_lists_the_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = harness.resolve_cell(b, "rcv1-tail.solve")
    assert cell.traffic["kind"] == "solve_ragged" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"pad_share", "update_roofline"} <= names
