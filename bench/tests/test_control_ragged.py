"""The output checks of ragged rows catch what they exist to catch, on
the CPU: a sound run passes; the bfloat16 CSR control fails; and a run
with the timed path broken underneath comes out not correct — a step
that returns its state unchanged, a gap over half the rows, and an
update altered where it is produced."""

from __future__ import annotations

import jax.numpy as jnp

from bench import harness
from bench.tests import cells
from bench.tests.test_gen_ragged import SMALL


def tail_cell(**traffic) -> harness.Cell:
    return cells.tiny_cell("rcv1-tail.solve", config=SMALL, **traffic)


def _failed(line) -> list:
    return [k for k, c in line["checks"].items()
            if isinstance(c["value"], str) or not c["value"] <= c["limit"]]


def test_sound_ragged_solve_is_correct():
    line = cells.run(tail_cell())
    assert line["correct"], line
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_bf16_control_fails_the_ragged_checks():
    line = cells.run(tail_cell(), control="bf16")
    assert not line["correct"]
    assert "inv" in _failed(line)


def test_fault_epoch_returns_state_unchanged(monkeypatch):
    from repro.core import sharded

    monkeypatch.setattr(sharded, "_epoch_scan",
                        lambda rounds, gap, carry, draw_perm, **kw: carry)
    line = cells.run(tail_cell(epoch_cap=8))
    assert not line["correct"]
    assert "gap" in _failed(line)


def test_fault_gap_over_half_the_rows(monkeypatch):
    from repro.core import sharded

    make = sharded._make_gap_ragged

    def half(loss, X_loc, axes=("data",)):
        gap = make(loss, X_loc, axes)

        def gap_half(rec, alpha_loc, mask, d_run, w_view, y=None):
            n = mask.shape[0]
            keep = mask & (jnp.arange(n) < n // 2)
            return gap(rec, alpha_loc, keep, d_run, w_view, y)

        return gap_half

    monkeypatch.setattr(sharded, "_make_gap_ragged", half)
    line = cells.run(tail_cell(epoch_cap=12))
    assert not line["correct"]
    assert "gap" in _failed(line) or line["failed"] >= 1


def test_fault_update_altered_where_produced(monkeypatch):
    from repro.core import sharded

    engine = sharded._local_block_update_ragged

    def altered(*args, **kw):
        alpha, dw = engine(*args, **kw)
        return alpha, dw * 1.001  # the primal drifts from w(alpha)

    monkeypatch.setattr(sharded, "_local_block_update_ragged", altered)
    line = cells.run(tail_cell())
    assert not line["correct"]
    assert "inv" in _failed(line)
