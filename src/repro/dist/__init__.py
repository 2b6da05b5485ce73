"""``repro.dist`` — the single home for all mesh / sharding / collective
policy.

PASSCoDe's contribution is how coordinate updates interact with a shared
primal vector under different memory models; on an SPMD mesh that
"memory model" *is* the sharding + collective policy.  This package owns
that policy for every layer of the repo:

  ``repro.dist.mesh``      mesh construction (every axis ``Auto``),
                           data-parallel axis helpers, solver meshes
  ``repro.dist.sharding``  logical-activation rules (``ShardingRules``),
                           param / batch / cache / optimizer shardings
  ``repro.dist.compat``    the repo's ``shard_map`` / ``cost_analysis``

Models only *consume* a ``ShardingRules`` object; solvers only consume
mesh helpers and ``shard_map``.  No other module constructs
``NamedSharding`` / ``PartitionSpec`` policy by hand.
"""

from repro.dist.compat import shard_map
from repro.dist.mesh import (
    auto_mesh,
    data_axes,
    dp_size,
    make_mesh,
    make_production_mesh,
    solver_mesh,
    solver_mesh_2d,
    solver_mesh_tasks,
    task_axis_policy,
)
from repro.dist.sharding import (
    NO_RULES,
    ShardingRules,
    batch_pspec,
    batch_sharding,
    cache_shardings,
    logits_sharding,
    named,
    opt_shardings,
    param_shardings,
    replicated,
    token_sharding,
)

__all__ = [
    "NO_RULES",
    "ShardingRules",
    "auto_mesh",
    "batch_pspec",
    "batch_sharding",
    "cache_shardings",
    "data_axes",
    "dp_size",
    "logits_sharding",
    "make_mesh",
    "make_production_mesh",
    "named",
    "opt_shardings",
    "param_shardings",
    "replicated",
    "shard_map",
    "solver_mesh",
    "solver_mesh_2d",
    "solver_mesh_tasks",
    "task_axis_policy",
    "token_sharding",
]
