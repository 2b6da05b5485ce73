"""The solver's named layers inside the compiled epoch, and the benchmark's
reduction of a device trace to per-layer runs (``bench/scopes.py``)."""

from __future__ import annotations

import ast
import os
import re
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes, trace_reduce  # noqa: E402
from repro.core import sharded  # noqa: E402
from repro.core.duals import Hinge  # noqa: E402
from repro.data.sparse import EllMatrix  # noqa: E402
from repro.dist.mesh import make_mesh  # noqa: E402

N, K, D, B = 96, 6, 200, 8
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _setup():
    rng = np.random.default_rng(0)
    ids = np.stack([rng.choice(D, K, replace=False) for _ in range(N)])
    vals = rng.standard_normal((N, K)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    return sharded.prepare_solver(
        EllMatrix(ids.astype(np.int32), vals, D), Hinge(C=1.0), mesh=mesh,
        use_kernel="auto", block_size=B, gap_every=1, record=True, seed=3)


@pytest.fixture(scope="module")
def epoch():
    """The solve driver's one-epoch program: the segmented ELL pipeline,
    compiled on the CPU, with its instructions as (name, shape, opcode,
    op_name)."""
    setup = _setup()
    fn = sharded.build_pipeline(setup, epochs=1, total_epochs=4,
                                segmented=True)
    st = sharded.init_pipeline_state(setup, total_epochs=4)
    compiled = fn.lower(setup.X, setup.sq_norms, st).compile()
    instrs = []
    for line in compiled.as_text().splitlines():
        m, o = INSTR.match(line), OP_NAME.search(line)
        if m and o:
            instrs.append((m.group(1), m.group(2), m.group(3), o.group(1)))
    return setup, fn, st, instrs


def _scoped(instrs, scope):
    return [i for i in instrs if scopes.scope_of(i[3]) == scope]


def test_merge_scope_holds_the_full_width_delta_psum_and_fold(epoch):
    setup, _, _, instrs = epoch
    merge = _scoped(instrs, sharded.SCOPE_MERGE)
    width = f"f32[{setup.w_len}]"
    subs = [i for i in merge if i[3].endswith("passcode.merge/sub")]
    assert subs and all(i[1].startswith(width) for i in subs)
    # the Δw subtraction is the block engine's return, nested in update
    assert all("passcode.update/passcode.merge/sub" in i[3] for i in subs)
    assert any(i[2] == "all-reduce" and i[3].endswith("passcode.merge/psum")
               for i in merge)
    assert any(i[3].endswith("passcode.merge/add") for i in merge)


def test_update_scope_holds_the_row_loop(epoch):
    _, _, _, instrs = epoch
    loops = [i for i in instrs if i[2] == "while"
             and i[3].endswith("passcode.update/while")]
    assert loops
    body = [i for i in _scoped(instrs, sharded.SCOPE_UPDATE)
            if "passcode.update/while/body" in i[3]]
    assert any(i[3].endswith("scatter-add") for i in body)


def test_perm_and_gap_scopes_hold_the_draw_and_the_record(epoch):
    _, _, _, instrs = epoch
    perm = _scoped(instrs, sharded.SCOPE_PERM)
    assert any("passcode.perm" in i[3] and "sort" in i[3] for i in perm)
    gap = _scoped(instrs, sharded.SCOPE_GAP)
    assert any(i[3].endswith("passcode.gap/cond/branch_1_fun/psum")
               for i in gap)
    assert any(i[2] == "dynamic-update-slice" or "dynamic_update_slice"
               in i[3] or "scatter" in i[3] for i in gap)
    assert {scopes.scope_of(i[3]) for i in instrs} >= set(scopes.SCOPES)


def test_trace_metadata_plane_maps_instructions_to_scopes(epoch, tmp_path):
    """The profiler stores the compiled module's HLO proto on the trace's
    metadata plane: the reader finds every scope there."""
    setup, fn, st, _ = epoch
    jax.block_until_ready(fn(setup.X, setup.sq_norms, st))
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(fn(setup.X, setup.sq_norms, st))
    jax.profiler.stop_trace()
    with open(trace_reduce.find_xplane(str(tmp_path)), "rb") as f:
        buf = f.read()
    mods = scopes.hlo_modules(buf)
    found = set()
    for name, proto in mods.items():
        found |= set(scopes.instruction_scopes(buf, proto).values())
    assert found >= set(scopes.SCOPES)


# ------------------------------------------------- hand-made device trace --

P, U, M, G = scopes.SCOPES


def _ops():
    """Device 0 over the slice (0, 200) ns: an edge perm run cut at 0; a
    rounds ``while`` (60 ns container) holding a scoped row-loop
    ``while`` of three update ops with an unscoped op among them, two
    merges and an unscoped op between runs; a gap run with 5 ns idle
    inside; a perm, an update and a merge; an edge update cut at 200."""
    return [
        ("perm.a", -5, 5, P),
        ("while.r", 6, 60, None),
        ("while.u", 10, 30, U),
        ("upd.1", 10, 15, U), ("upd.2", 20, 25, U),
        ("ds.1", 26, 28, None), ("upd.3", 28, 30, U),
        ("merge.1", 32, 36, M),
        ("ds.2", 38, 40, None),
        ("upd.4", 42, 50, U),
        ("merge.2", 52, 58, M),
        ("gap.1", 70, 90, G), ("gap.2", 95, 100, G),
        ("perm.b", 120, 130, P),
        ("upd.5", 135, 150, U), ("merge.3", 150, 160, M),
        ("upd.6", 190, 210, U),
    ]


def _spans():
    return [("bench.traced", 0, 200), ("bench.solve_init", 100, 119),
            ("passcode.init_state", 105, 115), ("bench.finalize", 160, 188),
            ("passcode.finalize", 165, 185)]


def test_runs_alternate_and_clip_at_the_slice_edges():
    runs, _ = scopes.scope_runs(_ops(), (0, 200))
    assert [(r[0], r[1], r[2], r[3]) for r in runs] == [
        (P, 0, 5, 5), (U, 10, 30, 20), (M, 32, 36, 4), (U, 42, 50, 8),
        (M, 52, 58, 6), (G, 70, 100, 25), (P, 120, 130, 10),
        (U, 135, 150, 15), (M, 150, 160, 10), (U, 190, 200, 10)]


def test_containers_and_unscoped_ops_neither_count_nor_break_a_run():
    runs, _ = scopes.scope_runs(_ops(), (0, 200))
    # the row loop's three ops (and the unscoped op among them) are one
    # run of three ops; the loop's control time between them counts
    assert runs[1][0] == U and runs[1][4] == 3 and runs[1][3] == 20
    leaves = [o[0] for o in scopes.leaf_ops(_ops())]
    assert "while.r" not in leaves and "while.u" not in leaves
    assert "ds.1" in leaves


def test_summary_counts_complete_runs_only():
    s = scopes.summarize(_ops(), (0, 200))
    sc = s["scopes"]
    assert sc[U]["complete_runs"] == 3
    assert sc[U]["busy_s"] == pytest.approx(43e-9)
    assert sc[U]["mean_run_s"] == pytest.approx(43e-9 / 3)
    assert sc[M]["complete_runs"] == 3
    assert sc[M]["mean_run_s"] == pytest.approx(20e-9 / 3)
    assert sc[P]["complete_runs"] == 1
    assert sc[P]["mean_run_s"] == pytest.approx(10e-9)
    assert sc[G]["complete_runs"] == 1
    assert sc[G]["mean_run_s"] == pytest.approx(25e-9)
    # busy outside every run: the rounds while's 4 + 2 + 6 + 2 + 2 ns
    assert s["unscoped_busy_s"] == pytest.approx(16e-9)
    assert s["busy_s"] == pytest.approx(129e-9)


def test_epoch_counts_between_consecutive_gap_runs():
    seq = [P, U, M, G, P] + [U, M] * 3 + [G, P, U, M, G, U]
    runs = [[sc, i, i + 1, 1.0, 1] for i, sc in enumerate(seq)]
    assert scopes.epoch_counts(runs) == [{P: 1, U: 3, M: 3}, {P: 1, U: 1,
                                                              M: 1}]


def test_idle_gap_labelled_by_the_innermost_program_span():
    flat = [(n, s, e) for n, s, e, _ in _ops()]
    gaps = trace_reduce.idle_gaps(flat, _spans(), (0, 200))
    assert gaps == [["passcode.finalize", pytest.approx(30e-9)],
                    ["passcode.init_state", pytest.approx(20e-9)],
                    ["bench.traced", pytest.approx(10e-9)],
                    ["bench.traced", pytest.approx(5e-9)],
                    ["bench.traced", pytest.approx(5e-9)],
                    ["bench.traced", pytest.approx(1e-9)]]


def test_harness_reduction_reads_the_same_with_scopes_and_spans():
    """busy, window, top ops and idle share of the harness's reduction are
    those of the same trace without the program's spans."""
    flat = [(n, s, e) for n, s, e, _ in _ops()]
    bench_only = [sp for sp in _spans() if sp[0].startswith("bench.")]
    with_spans = trace_reduce.reduce(
        {"devices": {0: flat}, "spans": _spans(), "op_line": "XLA Ops"},
        window_span="bench.traced")
    without = trace_reduce.reduce(
        {"devices": {0: flat}, "spans": bench_only, "op_line": "XLA Ops"},
        window_span="bench.traced")
    for key in ("busy_s", "window_s", "device_ops", "n_ops"):
        assert with_spans[key] == without[key]
    assert with_spans["busy_s"] == pytest.approx(129e-9)
    assert with_spans["window_s"] == pytest.approx(200e-9)
    summ = scopes.summarize(_ops(), (0, 200))
    assert summ["busy_s"] == pytest.approx(with_spans["busy_s"])
    from bench import harness

    idle = harness.load_reader("idle_share.solve")
    rec = {"kind": "solve", "trace": with_spans}
    assert idle(rec) == pytest.approx(100 * (1 - 129 / 200))


def test_scope_readers_read_nothing_without_a_matching_trace(tmp_path,
                                                            monkeypatch):
    from bench import harness

    monkeypatch.setattr(scopes, "newest_xplane", lambda: None)
    for name in ("update_device_us", "merge_us", "perm_ms",
                 "gap_device_ms"):
        read = harness.load_reader(name)
        assert read({"kind": "solve"}) is None
        assert read({"kind": "solve", "trace": {"n_ops": 3,
                                                "window_s": 1.0}}) is None


def test_reader_block_size_is_the_solve_driver_s():
    src = open(os.path.join(ROOT, "bench", "drivers", "solve.py")).read()
    calls = [n for n in ast.walk(ast.parse(src))
             if isinstance(n, ast.Call)
             and getattr(n.func, "attr", None) == "prepare_solver"]
    sizes = {kw.value.value for c in calls for kw in c.keywords
             if kw.arg == "block_size"}
    assert sizes == {scopes.SOLVE_BLOCK_SIZE}
    assert scopes.block_size({}) == scopes.SOLVE_BLOCK_SIZE
    assert scopes.block_size({"block_size": 32}) == 32


def test_instruction_name_and_scope_of():
    assert scopes.instruction_name(
        "%fusion.75 = f32[1355192]{0} fusion(...)") == "fusion.75"
    assert scopes.scope_of(
        "jit(f)/while/body/passcode.update/passcode.merge/sub") == M
    assert scopes.scope_of("jit(f)/while/body/add") is None


def test_program_host_spans_wrap_the_solve_boundaries(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    setup = _setup()
    st = sharded.init_pipeline_state(setup, total_epochs=2)
    jax.block_until_ready(sharded.finalize_state(setup, st,
                                                 epochs=2).alpha)
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    names = {e.name for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert {"passcode.prepare", "passcode.init_state",
            "passcode.finalize"} <= names
