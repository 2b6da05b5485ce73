"""Distributed PASSCoDe (shard_map) — semantics on 1 device in-process,
true multi-device semantics via an 8-host-device subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dcd_solve, sharded_passcode_solve
from repro.core.duals import Hinge
from repro.core.objective import duality_gap, w_of_alpha


def test_single_device_matches_serial_quality(tiny_dense, hinge):
    r = sharded_passcode_solve(tiny_dense, hinge, epochs=12, block_size=32)
    assert float(r.gaps[-1]) < 0.5
    # lossless psum ⇒ ŵ == w̄ (atomic semantics)
    w_bar = w_of_alpha(tiny_dense[: r.alpha.shape[0]], r.alpha)
    np.testing.assert_allclose(np.asarray(r.w_hat), np.asarray(w_bar),
                               rtol=1e-3, atol=1e-3)


def test_delayed_mode_still_converges(tiny_dense, hinge):
    r = sharded_passcode_solve(tiny_dense, hinge, epochs=15, block_size=32,
                               delay_rounds=1)
    assert float(r.gaps[-1]) < 1.0


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import sharded_passcode_solve, dcd_solve
    from repro.core.duals import Hinge
    from repro.core.objective import w_of_alpha
    from repro.data.synthetic import make_dataset

    assert len(jax.devices()) == 8
    ds = make_dataset("tiny")
    X = ds.dense_train()
    loss = Hinge(C=1.0)
    mesh = jax.make_mesh((8,), ("data",))
    # τ = 8 devices × 8-coordinate blocks = 64 ≪ n: inside the Thm 2
    # staleness regime (eq. 7) — must converge.
    r = sharded_passcode_solve(X, loss, mesh=mesh, epochs=12, block_size=8)
    gap = float(r.gaps[-1])
    assert gap < 0.8, f"8-device atomic PASSCoDe did not converge: {{gap}}"
    # τ = 128 = n/2: grossly violates eq. (7) — expect non-convergence.
    r_bad = sharded_passcode_solve(X, loss, mesh=mesh, epochs=12,
                                   block_size=16)
    assert float(r_bad.gaps[-1]) > 10 * gap, (
        "staleness bound did not bite: " + str(float(r_bad.gaps[-1])))
    w_bar = w_of_alpha(X[: r.alpha.shape[0]], r.alpha)
    eps = float(jnp.linalg.norm(r.w_hat - w_bar))
    assert eps < 1e-2, f"psum lost updates?! eps={{eps}}"
    # feature-sharded (model-parallel) variant == serial DCD semantics
    mesh_m = jax.make_mesh((8,), ("model",))
    r_m = sharded_passcode_solve(X, loss, mesh=mesh_m, epochs=8,
                                 block_size=X.shape[0], record=False)
    alpha, w = r_m.alpha, r_m.w_hat
    ref = dcd_solve(X, loss, epochs=8)
    from repro.core.objective import duality_gap
    g2 = float(duality_gap(alpha, X, loss))
    assert g2 < 1.0, g2
    print("SUBPROCESS_OK", gap, eps, g2)
""")


def test_multi_device_semantics_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SUBPROCESS.format(src=src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
