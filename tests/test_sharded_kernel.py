"""Fused (Pallas) vs unfused (jnp) sharded PASSCoDe — the two dense
block engines of the sharded solve must agree to atol 1e-5 for every loss
in the family and for delayed (stale-τ) rounds, in CPU interpret mode.

Multi-device agreement is covered by an 8-host-device subprocess, same
pattern as tests/test_sharded.py.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sharded_passcode_solve
from repro.core.duals import Hinge, Logistic, SquaredHinge
from repro.core.sharded import _resolve_kernel_mode
from repro.dist.mesh import dcd_block_rows, dcd_kernel_fits


@pytest.mark.parametrize("delay_rounds", [0, 1])
@pytest.mark.parametrize(
    "loss", [Hinge(C=1.0), SquaredHinge(C=1.0), Logistic(C=1.0)],
    ids=["hinge", "sq", "logistic"],
)
def test_use_kernel_equivalence(tiny_dense, loss, delay_rounds):
    kw = dict(epochs=2, block_size=32, delay_rounds=delay_rounds,
              record=False)
    r0 = sharded_passcode_solve(tiny_dense, loss, **kw)
    r1 = sharded_passcode_solve(tiny_dense, loss, use_kernel=True, **kw)
    np.testing.assert_allclose(np.asarray(r1.alpha), np.asarray(r0.alpha),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1.w_hat), np.asarray(r0.w_hat),
                               rtol=1e-5, atol=1e-5)
    assert r1.w_hat.shape == r0.w_hat.shape  # lane padding sliced off


def test_use_kernel_converges(tiny_dense, hinge):
    r = sharded_passcode_solve(tiny_dense, hinge, epochs=12, block_size=32,
                               use_kernel=True)
    assert float(r.gaps[-1]) < 0.5


def test_auto_mode_falls_back_on_cpu(tiny_dense, hinge):
    """"auto" must select the pure-jnp engine off-TPU (interpret mode is
    a semantics validator, not a fast path) and still solve."""
    use_k, interpret = _resolve_kernel_mode("auto", 128, 80)
    assert jax.default_backend() != "tpu"
    assert use_k is False and interpret is True
    r = sharded_passcode_solve(tiny_dense, hinge, epochs=3, block_size=32,
                               use_kernel="auto", record=False)
    assert r.w_hat.shape[0] == tiny_dense.shape[1]


def test_vmem_policy_helpers():
    # paper-dataset scale shards fit; a kddb-scale shard does not
    assert dcd_kernel_fits(4096, 512)
    assert not dcd_kernel_fits(100_000, 30_000)
    b = dcd_block_rows(8192)
    assert b & (b - 1) == 0 and 8 <= b <= 512
    # bigger d → smaller (or equal) row tile under the same budget
    assert dcd_block_rows(32768) <= dcd_block_rows(1024)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sharded_passcode_solve
    from repro.core.duals import Hinge
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    X = make_dataset("tiny").dense_train()
    loss = Hinge(C=1.0)
    mesh = jax.make_mesh((8,), ("data",))
    kw = dict(mesh=mesh, epochs=3, block_size=8, record=False)
    r0 = sharded_passcode_solve(X, loss, **kw)
    r1 = sharded_passcode_solve(X, loss, use_kernel=True, **kw)
    da = float(jnp.max(jnp.abs(r0.alpha - r1.alpha)))
    dw = float(jnp.max(jnp.abs(r0.w_hat - r1.w_hat)))
    assert da < 1e-5 and dw < 1e-5, (da, dw)
    print("SUBPROCESS_OK", da, dw)
""")


def test_multi_device_kernel_equivalence_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
