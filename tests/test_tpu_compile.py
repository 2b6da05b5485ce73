"""Compile the Pallas DCD kernels for a TPU v5e that is described, not
attached: Mosaic refuses here what it would refuse on the chip.

Each kernel family compiles at the shape ``chip_smoke.py`` runs on the
chip and at the largest shape its VMEM policy (``dcd_*_kernel_fits``)
admits — a row count for the VMEM-resident shards, the primal's width d
for the ELL kernel, which streams its rows from HBM — so the policy
cannot admit a shard the compiler rejects.  The ELL kernel also
compiles at both benchmark cells' full shapes.  The topology is described inside a fixture (never at
import), which keeps xdist workers collecting the same tests.
"""

import os

import jax
import jax.numpy as jnp
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
def chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
