"""Ragged rows (``CsrMatrix``) on the 1-D solve path.

Rows of unequal length are packed end to end, each padded only to the
walk's group of ``GRAIN`` slots (``pack_ragged``); the jnp engine, the
streamed Pallas kernel (interpret mode here) and the gap read each row's
own slots.  The solve must match serial DCD (``repro.core.dcd``) run in
the same update order, for every loss and for delayed rounds; the
kernel must match the jnp engine on short, chunked (longer than one SMEM
slot) and revisited rows; the packing must round-trip; a fixed-width
``EllMatrix`` must still compile to the program it compiled to before
ragged rows existed; and every path that takes fixed-width rows only
must refuse a ``CsrMatrix`` rather than pad it.
"""

import hashlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import lane_edge_rows, serial_replay
from repro.core import dcd_solve, sharded_passcode_solve
from repro.core import sharded
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.objective import duality_gap
from repro.data.sparse import (
    EllMatrix,
    csr_from_rows,
    ell_append,
    ell_repack,
    pack_ragged,
)
from repro.dist.mesh import (
    SMEM_BYTES,
    dcd_ragged_kernel_fits,
    dcd_ragged_kernel_smem_bytes,
    lane_pad,
    make_mesh,
)
from repro.kernels.dcd_ell import CHUNK_TILES, GRAIN, LANES, ragged_stream_rows
from repro.kernels.ops import dcd_ragged_block_update_pallas

LOSSES = [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)]
LOSS_IDS = ["hinge", "sq", "logistic"]


def _ragged(n=70, d=300, seed=0, long_rows=()):
    """n label-folded unit-norm rows of log-normal length over d
    features (an empty row among them), plus the lengths of
    ``long_rows``."""
    rng = np.random.default_rng(seed)
    lens = np.clip(np.rint(rng.lognormal(np.log(12), 0.8, n)), 1, d // 2)
    lens = lens.astype(int)
    lens[2] = 0
    for i, length in enumerate(long_rows):
        lens[3 + i] = length
    rows = []
    for length in lens:
        c = np.sort(rng.choice(d, length, replace=False))
        v = rng.standard_normal(length)
        v /= max(np.linalg.norm(v), 1e-12)
        rows.append((c, v * rng.choice([-1.0, 1.0])))
    return csr_from_rows(rows, d)


@pytest.fixture(scope="module")
def csr():
    return _ragged()


@pytest.mark.parametrize("delay_rounds", [0, 2])
@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_ragged_solve_matches_serial_dcd(csr, loss, delay_rounds):
    """One device: the packed solve is serial DCD in its draw order."""
    r = sharded_passcode_solve(csr, loss, epochs=2, block_size=16,
                               delay_rounds=delay_rounds, seed=5)
    assert r.engine == "ragged/jnp"
    ref = serial_replay(csr, loss, epochs=2, block_size=16, seed=5)
    np.testing.assert_allclose(np.asarray(r.alpha), np.asarray(ref.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r.w_hat), np.asarray(ref.w),
                               rtol=1e-5, atol=1e-5)
    # the recorded gap against the host's over the CSR rows (f32 sums in
    # another order: a logistic gap near 6e-4 differs by ~7e-6)
    g = float(duality_gap(ref.alpha, csr, loss))
    np.testing.assert_allclose(float(r.gaps[-1]), g, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_ragged_fused_matches_padded_ell(loss):
    """The streamed kernel (interpret mode) on packed rows, one of them
    longer than an SMEM chunk, against the jnp engine on the same rows
    padded to the longest (ELL)."""
    X = _ragged(n=40, d=1400, long_rows=(700,))
    kw = dict(epochs=2, block_size=16, seed=3, record=False)
    r_k = sharded_passcode_solve(X, loss, use_kernel=True, **kw)
    r_e = sharded_passcode_solve(X.to_ell(), loss, **kw)
    assert r_k.engine == "ragged/pallas-stream-interpret"
    np.testing.assert_allclose(np.asarray(r_k.alpha), np.asarray(r_e.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_k.w_hat), np.asarray(r_e.w_hat),
                               rtol=1e-5, atol=1e-5)


def test_dcd_solve_takes_ragged_rows(csr, hinge):
    """The serial oracle accepts a ``CsrMatrix``: the same iterates as on
    the padded rows, and its gap read over the unpadded ones."""
    r = dcd_solve(csr, hinge, epochs=3, seed=2)
    r_e = dcd_solve(csr.to_ell(), hinge, epochs=3, seed=2)
    np.testing.assert_array_equal(np.asarray(r.alpha), np.asarray(r_e.alpha))
    # the same α; the gaps summed over CSR and over ELL rows in f32
    np.testing.assert_allclose(np.asarray(r.gaps), np.asarray(r_e.gaps),
                               rtol=1e-4)


def _block_case(case: str, seed: int = 11):
    """One block's operands over packed rows for the kernel and the jnp
    engine: short rows, rows spanning 2–4 SMEM chunks, a revisiting
    block, and the active-mask and label operands; or, for
    ``lane_edges``, ``lane_edge_rows`` and an empty row, each visited
    once (the 600-slot row streams in two chunks)."""
    rng = np.random.default_rng(seed)
    if case == "lane_edges":
        d = 1023
        rows, vals, w = lane_edge_rows(d)
        X = csr_from_rows(list(zip(rows, vals)) + [((), ())], d)
        n = X.n_rows
        p = pack_ragged(X, 1, n, grain=GRAIN)
        sq = np.maximum(X.row_sq_norms(), 1.0).astype(np.float32)
        alpha = rng.uniform(0.0, 1.0, n).astype(np.float32)
        return p, jnp.asarray(sq), jnp.asarray(alpha), jnp.asarray(w), \
            jnp.asarray(rng.permutation(n).astype(np.int32)), None, None
    long_rows = (600, 1500, 513) if case == "chunked" else ()
    X = _ragged(n=40, d=1600, seed=seed, long_rows=long_rows)
    n = X.n_rows
    p = pack_ragged(X, 1, n, grain=GRAIN)
    sq = np.maximum(X.row_sq_norms(), 1e-12).astype(np.float32)
    sq[2] = 1.0  # the empty row
    b = 64
    if case == "revisit":
        idx = np.concatenate([rng.permutation(n), np.arange(b - n - 4),
                              [3, 3, 17, 17]])
    else:
        idx = np.concatenate([[3, 4, 5], rng.permutation(n)])[:b]
        idx = np.resize(idx, b)
    w = np.zeros(lane_pad(1600 + 1), np.float32)
    w[:1600] = 0.2 * rng.standard_normal(1600)
    alpha = rng.uniform(0.0, 1.0, n).astype(np.float32)
    act = y = None
    if case == "act_y":
        act = jnp.asarray(rng.random(n) > 0.3)
        y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    return p, jnp.asarray(sq), jnp.asarray(alpha), jnp.asarray(w), \
        jnp.asarray(idx.astype(np.int32)), act, y


@pytest.mark.parametrize("case", ["short", "chunked", "revisit", "act_y",
                                  "lane_edges"])
@pytest.mark.parametrize("loss", LOSSES, ids=LOSS_IDS)
def test_streamed_ragged_block_matches_jnp_engine(loss, case):
    """The same α and Δw as the jnp engine to atol 1e-5, and to the bit
    on the exact ``lane_edges`` rows."""
    p, sq, alpha, w, idx, act, y = _block_case(case)
    cols, vals = jnp.asarray(p.cols), jnp.asarray(p.vals)
    ptr, wid = jnp.asarray(p.ptr), jnp.asarray(p.wid)
    span = CHUNK_TILES * LANES
    tiles = -(-(p.ptr % LANES + p.wid) // LANES)
    if case == "chunked":
        assert (tiles * LANES > span).sum() >= 3  # rows in 2+ chunks
        assert (-(-tiles // CHUNK_TILES)).max() >= 3
    if case == "lane_edges":
        assert (-(-tiles // CHUNK_TILES)).max() == 2
    a_k, dw_k = dcd_ragged_block_update_pallas(
        ragged_stream_rows(cols, vals), ptr, wid, sq, alpha, w, idx,
        loss=loss, interpret=True,
        active=None if act is None else act.astype(jnp.float32), y=y)
    a_j, dw_j = sharded._local_block_update_ragged(
        (cols, vals, ptr, wid), sq, alpha, w, idx, loss, act=act, y=y)
    if case == "lane_edges":
        np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_j))
        np.testing.assert_array_equal(np.asarray(dw_k), np.asarray(dw_j))
        assert np.asarray(dw_k)[:-1].any()
    else:
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_j),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_j),
                                   rtol=1e-5, atol=1e-5)
    d = w.shape[0] - 1 if case == "lane_edges" else 1600
    assert float(jnp.abs(dw_k[d:]).max()) == 0.0  # dummy + lane pad


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 30),
    d=st.integers(1, 60),
    shards=st.integers(1, 4),
    density=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_ragged_round_trips(n, d, shards, density, seed):
    """Packing to shards and reading each row back through its first
    slot and width gives the dense matrix again; every slot past a row's
    nonzeros is padding; the group → row table names each group's row."""
    rng = np.random.default_rng(seed)
    dense = np.where(rng.random((n, d)) < density,
                     rng.standard_normal((n, d)), 0.0).astype(np.float32)
    X = csr_from_rows([(np.flatnonzero(r), r[r != 0]) for r in dense], d)
    np.testing.assert_array_equal(X.to_dense(), dense)
    n_loc = -(-n // shards)
    p = pack_ragged(X, shards, n_loc, grain=GRAIN)
    s_loc = p.cols.size // shards
    assert s_loc % LANES == 0 and p.gseg.size == p.cols.size // GRAIN
    back = np.zeros((shards * n_loc, d + 1), np.float32)
    for i in range(shards * n_loc):
        j, r = divmod(i, n_loc)
        lo = j * s_loc + p.ptr[i]
        np.add.at(back[i], p.cols[lo:lo + p.wid[i]], p.vals[lo:lo + p.wid[i]])
        assert np.all(p.gseg[lo // GRAIN:(lo + p.wid[i]) // GRAIN] == r)
    np.testing.assert_allclose(back[:n, :d], dense, rtol=0, atol=0)
    assert not back[n:, :d].any() and not back[:, d].any()
    lens = np.diff(np.asarray(X.indptr))
    np.testing.assert_array_equal(p.wid[:n], -(-lens // GRAIN) * GRAIN)
    assert p.nnz == lens.sum() and p.slots == p.wid.sum()


def test_layout_counters(csr):
    setup = sharded.prepare_solver(csr, Hinge(C=1.0))
    lens = csr.row_lengths()
    wid = -(-lens // GRAIN) * GRAIN
    lay = setup.layout
    assert setup.ragged and sharded.engine_name(setup) == "ragged/jnp"
    assert lay.nnz == lens.sum() and lay.slots_walked == wid.sum()
    assert lay.buckets == np.unique(wid[wid > 0]).size
    assert lay.chunked_rows == 0
    assert sharded.prepare_solver(csr.to_ell(), Hinge(C=1.0)).layout is None


def test_ragged_kernel_admission():
    """The ragged kernel keeps the ELL kernel's VMEM policy (d alone) and
    adds only its fixed SMEM row buffer: two chunk slots of ids and
    values, 8 KiB, whatever the rows' lengths."""
    assert dcd_ragged_kernel_smem_bytes(CHUNK_TILES) < 16 * 2**10
    assert dcd_ragged_kernel_fits(47_236, CHUNK_TILES)
    assert dcd_ragged_kernel_fits(1_355_191, CHUNK_TILES)
    assert not dcd_ragged_kernel_fits(16_609_143, CHUNK_TILES)
    assert not dcd_ragged_kernel_fits(47_236, SMEM_BYTES // 2048)
    use_k, interpret = sharded._resolve_kernel_mode(
        "auto", 100, 300, ragged=True)
    assert use_k is False and interpret is True  # the CPU falls back


# sha256 of the one-epoch pipeline's jaxpr for a fixed-width EllMatrix
# (source locations stripped) — the jnp engine as the program compiled
# before ragged rows existed, and the streamed kernel in interpret mode
# with the gap's products as the one-hot MXU pass (kernels/gap_onehot.py)
# and its walks splitting ids by shift and mask (kernels/dcd_ell.py);
# JAX 0.9.0.  A fixed-width matrix takes exactly the ELL path.
FIXED_JAXPR_SHA256 = {
    False: "60a2abe75fa494f7",
    True: "6059152d9b23ee1e",
}


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "pallas-stream"])
def test_fixed_width_program_unchanged(use_kernel):
    if jax.__version__ != "0.9.0":
        pytest.skip(f"the recorded jaxprs are JAX 0.9.0's, not "
                    f"{jax.__version__}'s")
    rng = np.random.default_rng(0)
    n, k, d = 100, 12, 300
    ids = np.stack([np.sort(rng.choice(d, k, replace=False))
                    for _ in range(n)]).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    setup = sharded.prepare_solver(EllMatrix(ids, vals, d), Hinge(C=1.0),
                                   use_kernel=use_kernel, block_size=16,
                                   seed=3)
    fn = sharded.build_pipeline(setup, epochs=1, total_epochs=10,
                                segmented=True)
    st = sharded.init_pipeline_state(setup, total_epochs=10)
    txt = str(jax.make_jaxpr(fn)(setup.X, setup.sq_norms, st))
    txt = re.sub(r" at [^ \n]+:\d+", "", txt)
    got = hashlib.sha256(txt.encode()).hexdigest()[:16]
    assert got == FIXED_JAXPR_SHA256[use_kernel]


def _refusal(what, csr):
    loss = Hinge(C=1.0)
    if what == "2d":
        return lambda: sharded.prepare_solver(
            csr, loss, mesh=make_mesh((1, 1), ("data", "model")))
    if what == "pod":
        return lambda: sharded.prepare_solver(
            csr, loss, mesh=make_mesh((1, 1), ("pod", "data")))
    if what == "task":
        return lambda: sharded_passcode_solve(
            csr, loss, y=np.ones((2, csr.n_rows), np.float32))
    if what == "shrink":
        return lambda: sharded.prepare_solver(csr, loss, shrink_every=1)
    if what == "ell_append":
        return lambda: ell_append(csr.to_ell(), csr)
    if what == "ell_repack":
        return lambda: ell_repack(csr, 500)
    if what == "serve":
        from repro.serve.trainer import IncrementalTrainer

        return lambda: IncrementalTrainer(csr, loss)
    raise AssertionError(what)


@pytest.mark.parametrize("what", ["2d", "pod", "task", "shrink",
                                  "ell_append", "ell_repack", "serve"])
def test_paths_without_ragged_rows_refuse_them(csr, what):
    with pytest.raises((ValueError, TypeError), match="CsrMatrix"):
        _refusal(what, csr)()


_SUBPROCESS = textwrap.dedent("""
    import os
    import sys
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {tests!r})
    # the test module imports the suite's conftest, which clears
    # XLA_FLAGS: set them after it, before the first device query
    from test_sharded_ragged import _ragged
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.dist.mesh import make_mesh

    assert len(jax.devices()) == 8
    mesh = make_mesh((8,), ("data",))
    # 83 rows over 8 devices: a padded tail, and a row longer than one
    # SMEM chunk on the fused path
    X = _ragged(n=83, d=1200, long_rows=(560, 9))
    kw = dict(mesh=mesh, epochs=3, block_size=4, seed=4)
    r_e = sharded_passcode_solve(X.to_ell(), Hinge(C=1.0), **kw)
    for uk in (False, True):
        r = sharded_passcode_solve(X, Hinge(C=1.0), use_kernel=uk, **kw)
        np.testing.assert_allclose(np.asarray(r.alpha),
                                   np.asarray(r_e.alpha), atol=1e-5)
        np.testing.assert_allclose(np.asarray(r.w_hat),
                                   np.asarray(r_e.w_hat), atol=1e-5)
        # gaps summed in f32 in another order
        np.testing.assert_allclose(np.asarray(r.gaps),
                                   np.asarray(r_e.gaps), rtol=1e-4,
                                   atol=1e-4)
    assert r.alpha.shape == (83,) and float(r.gaps[-1]) < float(r.gaps[0])
    print("SUBPROCESS_OK", r.engine, float(r.gaps[-1]))
""")


def test_ragged_multi_device_subprocess():
    """Eight devices: the packed solve (jnp and fused) runs the update
    sequence of the padded-ELL solve, tail rows included."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = _SUBPROCESS.format(src=os.path.join(here, "..", "src"),
                              tests=here)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
