"""Compile the Pallas DCD kernels for a TPU v5e that is described, not
attached: Mosaic refuses here what it would refuse on the chip.

Each kernel family compiles at the shape ``chip_smoke.py`` runs on the
chip and at the largest row count its VMEM policy (``dcd_*_kernel_fits``)
admits for those widths, so the policy cannot admit a shard the
compiler rejects.  The topology is described inside a fixture (never at
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
from repro.kernels.dcd_ell import dcd_ell_epoch_pallas_call
from repro.kernels.dcd_feature import (
    dcd_feature_gram_pallas_call,
    dcd_feature_update_pallas_call,
)

B = 64  # the solver's default block size
D_RCV1 = 47_236  # rcv1 width (paper Table 3)
K_RCV1 = 128  # rcv1's 73 nonzeros per row, lane-padded
DENSE_D = 1024


def _frontier(fits) -> int:
    """Largest n_loc with ``fits(n_loc)`` (policies are monotone in n)."""
    lo, hi = 1, 1 << 24
    assert fits(lo) and not fits(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo


N_DENSE = _frontier(lambda n: dcd_kernel_fits(n, DENSE_D))
N_ELL = _frontier(lambda n: dcd_ell_kernel_fits(n, K_RCV1, D_RCV1))
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


@pytest.mark.parametrize("loss", [Hinge(C=1.0), Logistic(C=1.0)],
                         ids=["hinge", "logistic"])
@pytest.mark.parametrize("n", [8192, N_ELL], ids=["smoke", "frontier"])
def test_dcd_ell_compiles(chip, n, loss):
    d1 = lane_pad(D_RCV1 + 1)
    hlo = _compile_text(
        chip,
        lambda c, v, a, w, q, i: dcd_ell_epoch_pallas_call(
            c, v, a, w, q, loss=loss, idx=i, block_rows=B),
        ((n, K_RCV1), I32), ((n, K_RCV1), F32), ((n,), F32), ((d1,), F32),
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

    def ell(c, v, a, w, q, i):
        return dcd_ell_epoch_pallas_call(c, v, a, w, q, loss=Hinge(C=1.0),
                                         idx=i, block_rows=B)

    hlo = _compile_text(
        chip, jax.vmap(ell, in_axes=(None, None, 0, 0, None, None)),
        ((n, K_RCV1), I32), ((n, K_RCV1), F32), ((K, n), F32),
        ((K, d1), F32), ((n,), F32), ((B,), I32))
    assert "tpu_custom_call" in hlo
