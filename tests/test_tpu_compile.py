"""Compile the Pallas DCD kernels for a TPU v5e that is described, not
attached: Mosaic refuses here what it would refuse on the chip.

Each kernel family compiles at the shape ``chip_smoke.py`` runs on the
chip and at the largest shape its VMEM policy (``dcd_*_kernel_fits``)
admits — a row count for the VMEM-resident shards, the primal's width d
for the ELL kernel, which streams its rows from HBM — so the policy
cannot admit a shard the compiler rejects.  The ELL kernel also
compiles at both benchmark cells' full shapes.  The gap's one-hot passes
compile at the rcv1 cells' full shapes and at the widest primal
``R_MAX`` admits, with no copy of the shard.  The topology is described
inside a fixture (never at import), which keeps xdist workers collecting
the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.duals import Hinge, Logistic
from repro.dist.mesh import (
    dcd_ell_kernel_fits,
    dcd_feature_kernel_fits,
    dcd_kernel_fits,
    lane_pad,
)
from repro.kernels.dcd_block import dcd_epoch_pallas_call
from repro.kernels.dcd_ell import dcd_ell_block_pallas_call
from repro.kernels.dcd_feature import (
    dcd_feature_gram_pallas_call,
    dcd_feature_update_pallas_call,
)

B = 64  # the solver's default block size
D_RCV1 = 47_236  # rcv1 width (paper Table 3)
K_RCV1 = 128  # rcv1's 73 nonzeros per row, lane-padded
DENSE_D = 1024
# (n, k_max, d) of the paper's Table 3 problems, as the benchmark runs them
RCV1_FULL = (677_399, 73, 47_236)
NEWS20_FULL = (16_000, 455, 1_355_191)


def _frontier(fits) -> int:
    """Largest n_loc with ``fits(n_loc)`` (policies are monotone in n)."""
    lo, hi = 1, 1 << 24
    assert fits(lo) and not fits(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo


N_DENSE = _frontier(lambda n: dcd_kernel_fits(n, DENSE_D))
D_ELL = _frontier(dcd_ell_kernel_fits)  # the ELL policy bounds d alone
N_FEAT = _frontier(lambda n: dcd_feature_kernel_fits(
    n, K_RCV1, D_RCV1, block_size=B))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("n", [2048, N_DENSE], ids=["smoke", "frontier"])
def test_dcd_block_compiles(chip, n):
    hlo = _compile_text(
        chip,
        lambda X, a, w, q, i: dcd_epoch_pallas_call(
            X, a, w, q, loss=Hinge(C=1.0), idx=i, block_rows=B),
        ((n, DENSE_D), F32), ((n,), F32), ((DENSE_D,), F32), ((n,), F32),
        ((B,), I32))
    assert "tpu_custom_call" in hlo


def _ell_block(loss, k):
    def f(c, v, a, w, q, i):
        return dcd_ell_block_pallas_call((c, v), a, w, q, i, k=k, loss=loss)
    return f


@pytest.mark.parametrize("loss", [Hinge(C=1.0), Logistic(C=1.0)],
                         ids=["hinge", "logistic"])
@pytest.mark.parametrize("n,k,d", [RCV1_FULL, NEWS20_FULL, (8192, 73, D_ELL)],
                         ids=["rcv1", "news20", "frontier"])
def test_dcd_ell_compiles(chip, n, k, d, loss):
    d1 = lane_pad(d + 1)
    kp = lane_pad(k)
    hlo = _compile_text(
        chip, _ell_block(loss, k),
        ((n, 1, kp), I32), ((n, 1, kp), F32), ((n,), F32), ((d1,), F32),
        ((n,), F32), ((B,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [8192, N_FEAT], ids=["smoke", "frontier"])
def test_dcd_feature_gram_compiles(chip, n):
    d1 = lane_pad(D_RCV1 + 1)
    hlo = _compile_text(
        chip,
        lambda c, v, w, i: dcd_feature_gram_pallas_call(c, v, w, i),
        ((n, K_RCV1), I32), ((n, K_RCV1), F32), ((d1,), F32), ((B,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [8192, N_FEAT], ids=["smoke", "frontier"])
def test_dcd_feature_update_compiles(chip, n):
    d1 = lane_pad(D_RCV1 + 1)
    hlo = _compile_text(
        chip,
        lambda c, v, a, q, w, i, b, g: dcd_feature_update_pallas_call(
            c, v, a, q, w, i, b, g, loss=Hinge(C=1.0)),
        ((n, K_RCV1), I32), ((n, K_RCV1), F32), ((n,), F32), ((n,), F32),
        ((d1,), F32), ((B,), I32), ((B,), F32), ((B, B), F32))
    assert "tpu_custom_call" in hlo


def test_kernels_batch_over_tasks(chip):
    """The multi-task pipeline vmaps the kernels over K heads."""
    n, K = 8192, 4
    d1 = lane_pad(D_RCV1 + 1)
    ell = _ell_block(Hinge(C=1.0), 73)
    hlo = _compile_text(
        chip, jax.vmap(ell, in_axes=(None, None, 0, 0, None, None)),
        ((n, 1, K_RCV1), I32), ((n, 1, K_RCV1), F32), ((K, n), F32),
        ((K, d1), F32), ((n,), F32), ((B,), I32))
    assert "tpu_custom_call" in hlo


# rcv1-tail (bench/configs/rcv1-tail.json): rcv1's 677,399 rows with
# log-normal lengths, packed to each row's length rounded up to 16 slots
# (54,642,784 slots at seed 2^33 + 5).  The longest row the law allows
# (4,096 ids, from any offset in its first lane tile) spans 9 SMEM chunks;
# the chunk loop's trip count is read at run time, so one compile covers
# every row length.
RCV1_TAIL = (677_399, 54_642_784, 47_236)


def _ragged_block(loss):
    from repro.kernels.dcd_ell import (
        dcd_ragged_block_pallas_call,
        ragged_stream_rows,
    )

    def f(c, v, p, wid, a, w, q, i):
        return dcd_ragged_block_pallas_call(
            ragged_stream_rows(c, v), p, wid, a, w, q, i, loss=loss)
    return f


@pytest.mark.parametrize("loss", [Hinge(C=1.0), Logistic(C=1.0)],
                         ids=["hinge", "logistic"])
def test_dcd_ragged_compiles(chip, loss):
    from repro.kernels.dcd_ell import CHUNK_TILES, GRAIN, LANES

    n, slots, d = RCV1_TAIL
    longest = 4096
    assert -(-(LANES - GRAIN + longest) // (CHUNK_TILES * LANES)) == 9
    s = lane_pad(slots)
    d1 = lane_pad(d + 1)
    hlo = _compile_text(
        chip, _ragged_block(loss),
        ((s,), I32), ((s,), F32), ((n,), I32), ((n,), I32), ((n,), F32),
        ((d1,), F32), ((n,), F32), ((B,), I32))
    assert "tpu_custom_call" in hlo


# opcodes that move no data: a shard-sized one of these is free
_FREE_OPS = {"parameter", "bitcast", "get-tuple-element", "tuple",
             "custom-call"}
_INSTR = re.compile(r"^\s*(?:ROOT )?\S+ = ([a-z0-9]+)\[([\d,]+)\]\S* "
                    r"([\w-]+)\(", re.M)


def _shard_sized(hlo: str, elems: int) -> list:
    """(opcode, dtype, dims) of every instruction of at least ``elems``
    elements that moves data — any opcode but a parameter, a bitcast, a
    tuple's element and a (tpu) custom call: a copy, transpose or fusion
    of that size relays the shard out."""
    out = []
    for m in _INSTR.finditer(hlo):
        dtype, dims, op = m.groups()
        if (op not in _FREE_OPS
                and int(np.prod([int(x) for x in dims.split(",")]))
                >= elems):
            out.append((op, dtype, dims))
    return sorted(out)


def _gap_products(layout: str, d_run: int, tasks: int = 0):
    from repro.kernels.dcd_ell import GRAIN, LANES
    from repro.kernels.gap_onehot import onehot_mv, onehot_rmv

    def ell(c, v, a, w):
        rmv = (lambda a: onehot_rmv(c, v, d_run, a))
        if tasks:
            rmv = jax.vmap(rmv)
        return rmv(a), onehot_mv(c, v, w, row_sum=True)

    def ragged(c, v, a, w):
        c, v = c.reshape(-1, LANES), v.reshape(-1, LANES)
        return (onehot_rmv(c, v, d_run, a.reshape(-1, LANES // GRAIN)),
                onehot_mv(c, v, w, row_sum=False))

    return ell if layout == "ell" else ragged


@pytest.mark.parametrize("case", ["rcv1", "rcv1-tail", "frontier", "tasks"])
def test_gap_onehot_compiles(chip, case):
    """The gap's one-hot passes at rcv1's and rcv1-tail's full shapes,
    at the widest admitted primal, and vmapped over task heads: Mosaic
    takes them, and no instruction of the shard's size moves data (the
    ELL shard is read in the column-major layout XLA keeps it in, the
    packed slots in their (slots / 128, 128) view)."""
    from repro.kernels.dcd_ell import GRAIN
    from repro.kernels.gap_onehot import R_MAX

    n, k, d = RCV1_FULL
    d_run, tasks = lane_pad(d + 1), 0
    if case == "rcv1-tail":
        n, s = RCV1_TAIL[0], lane_pad(RCV1_TAIL[1])
        shapes = (((s,), I32), ((s,), F32), ((s // GRAIN,), F32),
                  ((d_run,), F32))
        entries = s
    else:
        if case == "frontier":
            n, d_run = 8192, 128 * R_MAX
        if case == "tasks":
            tasks = 4
        a = (tasks, n) if tasks else (n,)
        shapes = (((n, k), I32), ((n, k), F32), (a, F32), ((d_run,), F32))
        entries = n * k
    fn = _gap_products("ragged" if case == "rcv1-tail" else "ell", d_run,
                       tasks)
    hlo = _compile_text(chip, fn, *shapes)
    assert hlo.count("tpu_custom_call") >= 2
    assert _shard_sized(hlo, entries) == []


def _pipeline_hlo(topo, p: int, n_loc: int, *, record: bool) -> str:
    """The rcv1 one-epoch pipeline (k = 73, d = 47,236; ``n_loc`` rows a
    device) compiled for ``p`` described chips on a ``data`` mesh: a
    CPU-prepared setup with the described mesh and compiled kernels put
    in, lowered from its arrays' shapes and shardings."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import sharded
    from repro.data.sparse import EllMatrix

    n, k, d = p * n_loc, RCV1_FULL[1], RCV1_FULL[2]
    rng = np.random.default_rng(0)
    ids = np.sort(rng.integers(0, d, (n, k)), axis=1).astype(np.int32)
    vals = rng.standard_normal((n, k)).astype(np.float32)
    setup = sharded.prepare_solver(EllMatrix(ids, vals, d), Hinge(C=1.0),
                                   use_kernel=True, seed=3, record=record)
    state = sharded.init_pipeline_state(setup, total_epochs=1)
    mesh = Mesh(np.array(topo.devices[:p]), ("data",),
                axis_types=(AxisType.Auto,))
    on_chip = setup._replace(
        mesh=mesh, p=p, n_loc=n_loc, interpret=False,
        n_blocks=sharded._n_blocks(n_loc, setup.block_size))
    fn = sharded.build_pipeline(on_chip, epochs=1, total_epochs=1,
                                segmented=True)
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def spec(a, sh):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    args = (jax.tree.map(lambda a: spec(a, rows), setup.X),
            spec(setup.sq_norms, rows),
            {key: spec(v, rows if key in ("alpha", "act") else rep)
             for key, v in state.items()})
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("p", [1, 4], ids=["one-chip", "data4"])
def test_gap_onehot_pipeline_adds_no_relayout(topo, p):
    """In the rcv1 one-epoch pipeline, on one chip and on a 2x2 ``data``
    mesh, the gap's passes add no shard-sized instruction that moves
    data: the recording program's are those of the program without the
    gap (the block engine's lane-tile layout of the shard), so the gap
    reads the shard as placed, inside the ``shard_map`` too."""
    from collections import Counter

    from repro.kernels.gap_onehot import gap_onehot_fits

    n_loc = 8192
    assert gap_onehot_fits(lane_pad(RCV1_FULL[2] + 1))
    entries = n_loc * RCV1_FULL[1]
    with_gap = _pipeline_hlo(topo, p, n_loc, record=True)
    without = _pipeline_hlo(topo, p, n_loc, record=False)
    # the gap's two passes are custom calls beside the block engine's
    assert (with_gap.count("tpu_custom_call")
            > without.count("tpu_custom_call"))
    extra = (Counter(_shard_sized(with_gap, entries))
             - Counter(_shard_sized(without, entries)))
    assert not extra, extra
