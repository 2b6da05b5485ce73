"""``shard_map`` and ``cost_analysis`` as the rest of the repo calls them.

Every shard_map call goes through :func:`shard_map` below, so solver
code names one entry point; ``cost_analysis`` flattens a compiled
program's cost report to a dict.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword-only mesh/specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}
