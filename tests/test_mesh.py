"""repro.dist.mesh: data-parallel axis helpers on single- and multi-pod
meshes, the launch-layer re-export shim, and shard_map compat."""

import jax
import numpy as np

from repro.dist.compat import shard_map
from repro.dist.mesh import data_axes, dp_size, solver_mesh


def _fake_mesh(shape, axes):
    """Abstract mesh over fake devices — fine for axis arithmetic."""
    devs = np.asarray(jax.devices() * int(np.prod(shape)))[: int(np.prod(shape))]
    return jax.sharding.Mesh(devs.reshape(shape), axes)


def test_data_axes_single_pod():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    assert data_axes(mesh) == ("data",)
    assert dp_size(mesh) == 16


def test_data_axes_multi_pod():
    mesh = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    assert data_axes(mesh) == ("pod", "data")
    assert dp_size(mesh) == 32


def test_data_axes_model_only():
    mesh = _fake_mesh((8,), ("model",))
    assert data_axes(mesh) == ()
    assert dp_size(mesh) == 1


def test_solver_mesh_axes():
    mesh = solver_mesh("data")
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == len(jax.devices())
    assert solver_mesh("model").axis_names == ("model",)


def test_launch_mesh_shim_reexports():
    from repro.launch import mesh as shim

    assert shim.data_axes is data_axes
    assert shim.dp_size is dp_size


def test_shard_map_compat_resolves():
    # end-to-end: a psum over a 1-device mesh round-trips
    mesh = solver_mesh("data")
    from jax.sharding import PartitionSpec as P

    n = len(jax.devices())
    out = shard_map(
        lambda x: jax.lax.psum(x.sum(), "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
    )(jax.numpy.arange(float(n)))
    assert float(out) == n * (n - 1) / 2


def test_lane_pad_public_and_overlap_policy():
    """PR 5: ``lane_pad`` is public API (core/benchmarks used to import
    the underscored spelling across modules) and the pipeline-overlap
    policy resolves/validates the solver's ``overlap`` knob."""
    import pytest

    from repro.dist.mesh import _lane_pad, lane_pad, pipeline_overlap

    assert lane_pad(1) == 128 and lane_pad(128) == 128
    assert lane_pad(129) == 256 and lane_pad(0) == 0
    assert _lane_pad is lane_pad  # back-compat alias
    # "auto": on exactly for (2-D, fused, delayed)
    assert pipeline_overlap("auto", two_d=True, fused=True, delay_rounds=1)
    for kw in (dict(two_d=False, fused=True, delay_rounds=1),
               dict(two_d=True, fused=False, delay_rounds=1),
               dict(two_d=True, fused=True, delay_rounds=0)):
        assert not pipeline_overlap("auto", **kw)
        with pytest.raises(ValueError):
            pipeline_overlap(True, **kw)
    assert pipeline_overlap(True, two_d=True, fused=True, delay_rounds=1)
    assert not pipeline_overlap(False, two_d=True, fused=True,
                                delay_rounds=1)


def test_serve_admission_policy_validates():
    import pytest

    from repro.dist.mesh import serve_admission_policy

    ok = serve_admission_policy(queue_depth=8, max_batch=4,
                                deadline_s=0.5, swap_grace_s=0.0)
    assert ok == {"queue_depth": 8, "max_batch": 4, "deadline_s": 0.5,
                  "swap_grace_s": 0.0}
    for bad in (dict(queue_depth=0, max_batch=4, deadline_s=1.0,
                     swap_grace_s=1.0),
                dict(queue_depth=8, max_batch=0, deadline_s=1.0,
                     swap_grace_s=1.0),
                dict(queue_depth=8, max_batch=4, deadline_s=0.0,
                     swap_grace_s=1.0),
                dict(queue_depth=8, max_batch=4, deadline_s=1.0,
                     swap_grace_s=-1.0)):
        with pytest.raises(ValueError):
            serve_admission_policy(**bad)


def test_serve_degrade_ladder_rungs():
    from repro.dist.mesh import serve_degrade_ladder

    r0 = serve_degrade_ladder(0, max_batch=64)
    assert r0 == {"rung": 0, "max_batch": 64, "train": True}
    r1 = serve_degrade_ladder(1, max_batch=64)
    assert r1 == {"rung": 1, "max_batch": 16, "train": True}
    r2 = serve_degrade_ladder(2, max_batch=64)
    assert r2 == {"rung": 2, "max_batch": 16, "train": False}
    # above-top rungs clamp; the live batch never drops below 1
    assert serve_degrade_ladder(9, max_batch=64)["rung"] == 2
    assert serve_degrade_ladder(1, max_batch=2)["max_batch"] == 1


def test_serve_rung_hysteresis():
    from repro.dist.mesh import serve_rung

    # climbs at the up thresholds
    assert serve_rung(0.0, 0) == 0
    assert serve_rung(0.5, 0) == 1
    assert serve_rung(0.9, 0) == 2
    # dead band: once at rung 1, 0.4 (>= down[0]=0.2) holds rung 1
    assert serve_rung(0.4, 1) == 1
    assert serve_rung(0.1, 1) == 0  # below down[0] -> descend
    # once at rung 2, 0.7 (>= down[1]=0.6) holds; 0.3 drops to 1
    assert serve_rung(0.7, 2) == 2
    assert serve_rung(0.3, 2) == 1
    assert serve_rung(0.05, 2) == 0  # falls through both bands


def test_drift_trip_thresholds():
    import jax.numpy as jnp

    from repro.dist.mesh import drift_trip

    # below ratio*base+floor: no trip; monotone in err_new
    assert int(drift_trip(jnp.float32(0.1), jnp.float32(0.2))) == 0
    assert int(drift_trip(jnp.float32(0.1), jnp.float32(0.26))) == 1
    # the floor absorbs small-sample noise on a perfect baseline
    assert int(drift_trip(jnp.float32(0.0), jnp.float32(0.04))) == 0
    assert int(drift_trip(jnp.float32(0.0), jnp.float32(0.06))) == 1
    assert int(drift_trip(jnp.float32(0.0), jnp.float32(0.5),
                          ratio=2.0, floor=0.6)) == 0


def test_solver_meshes_are_auto():
    """Every mesh ``repro.dist`` builds has Auto axes — under Explicit
    axes (jax's default) the solver's un-padding gathers cannot resolve
    an output sharding."""
    from jax.sharding import AxisType

    from repro.dist.mesh import solver_mesh_2d, solver_mesh_3d

    for mesh in (solver_mesh("data"), solver_mesh_2d(),
                 solver_mesh_3d(pod=1, n_devices=1)):
        assert all(t == AxisType.Auto for t in mesh.axis_types), mesh


def test_auto_mesh_normalises_a_caller_mesh():
    from jax.sharding import AxisType

    from repro.dist.mesh import auto_mesh, make_mesh

    explicit = jax.make_mesh((1, 1), ("pod", "data"),
                             axis_types=(AxisType.Explicit,) * 2)
    mesh = auto_mesh(explicit)
    assert mesh.axis_names == ("pod", "data")
    assert list(mesh.devices.flat) == list(explicit.devices.flat)
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    auto = make_mesh((1,), ("data",))
    assert auto_mesh(auto) is auto
