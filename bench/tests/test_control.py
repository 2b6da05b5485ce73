"""The output checks catch what they exist to catch, on the CPU.

A sound run passes; the bfloat16 control fails; and a run with the
timed path broken underneath comes out not correct, once for each fault
a cell can have: a step that returns its state unchanged, a reduction
over half the rows, the exchange between chips left out, and an answer
altered where it is produced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from bench.tests import cells


def _failed(line) -> list:
    """Names of the checks over their limit (a non-finite reading is
    text in the line, and over any limit)."""
    return [k for k, c in line["checks"].items()
            if isinstance(c["value"], str) or not c["value"] <= c["limit"]]


@pytest.mark.parametrize("data", [1, 4])
def test_sound_solve_is_correct(data):
    line = cells.run(cells.tiny_cell("rcv1.solve") if data == 1
                     else cells.data4_cell())
    assert line["correct"], line
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_sound_serve_is_correct():
    line = cells.run(cells.serve_cell(rate_rps=400.0))
    assert line["correct"], line
    assert line["failed"] == 0


def test_bf16_control_fails_the_solve_checks():
    line = cells.run(cells.tiny_cell("rcv1.solve"), control="bf16")
    assert not line["correct"]
    assert "inv" in _failed(line)


def test_bf16_control_fails_the_score_check():
    line = cells.run(cells.serve_cell(rate_rps=400.0),
                     control="bf16")
    assert not line["correct"]
    assert _failed(line) == ["score"]


def test_fault_epoch_returns_state_unchanged(monkeypatch):
    from repro.core import sharded

    def frozen(rounds, gap, carry, draw_perm, **kw):
        return carry

    monkeypatch.setattr(sharded, "_epoch_scan", frozen)
    line = cells.run(cells.tiny_cell("rcv1.solve", epoch_cap=8))
    # the gap buffer is never written either: its zero reads as "done",
    # and the float64 gap of the unchanged alpha = 0 is g(0)
    assert not line["correct"]
    assert "gap" in _failed(line)


def test_fault_gap_over_half_the_rows(monkeypatch):
    from repro.core import sharded

    make = sharded._make_gap_1d

    def half(loss, X_loc, ell, axes=("data",)):
        gap = make(loss, X_loc, ell, axes)

        def gap_half(rec, alpha_loc, mask, d_run, w_view, y=None):
            n = mask.shape[0]
            keep = mask & (jnp.arange(n) < n // 2)
            return gap(rec, alpha_loc, keep, d_run, w_view, y)

        return gap_half

    monkeypatch.setattr(sharded, "_make_gap_1d", half)
    line = cells.run(cells.tiny_cell("rcv1.solve", epoch_cap=12))
    # the half gap never reaches the target, or reads below the float64
    # gap of the same alpha
    assert not line["correct"]
    assert "gap" in _failed(line) or line["failed"] >= 1


def test_fault_exchange_between_chips_left_out(monkeypatch):
    from repro.core import sharded

    assert len(jax.devices()) >= 4
    scan = sharded._scan_rounds
    psum = jax.lax.psum

    def no_exchange(*args, **kw):
        monkeypatch.setattr(jax.lax, "psum", lambda x, axes: x)
        try:
            return scan(*args, **kw)
        finally:
            monkeypatch.setattr(jax.lax, "psum", psum)

    monkeypatch.setattr(sharded, "_scan_rounds", no_exchange)
    line = cells.run(cells.data4_cell(epoch_cap=12))
    # each device's w misses the others' updates: the solve stalls, or
    # its w drifts from w(alpha)
    assert not line["correct"]
    assert "inv" in _failed(line) or line["failed"] >= 1


def test_fault_score_altered_where_produced(monkeypatch):
    from repro.serve import engine

    make = engine._score_fn

    def altered(k_max):
        score = make(k_max)

        @functools.wraps(score)
        def wrong(w_pad, cols, vals):
            return score(w_pad, cols, vals).at[0, 0].add(1e-3)

        return wrong

    monkeypatch.setattr(engine, "_score_fn", altered)
    line = cells.run(cells.serve_cell(rate_rps=400.0))
    assert not line["correct"]
    assert _failed(line) == ["score"]


@pytest.mark.parametrize("workload", ["news20.solve", "rcv1.serve"])
def test_traced_run_without_a_device_plane_fails(workload):
    """The traced path runs to its reduction; on the CPU the trace has no
    TPU plane, and a traced run with no device op is refused."""
    from bench import harness

    cell = (cells.serve_cell(trace_seconds=0.5, rate_rps=400.0)
            if workload == "rcv1.serve"
            else cells.tiny_cell(workload, trace_seconds=0.5))
    with pytest.raises(harness.HarnessError, match="no device operation"):
        cells.run(cell, trace=True)
