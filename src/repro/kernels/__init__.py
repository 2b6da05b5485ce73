"""Pallas TPU kernels for PASSCoDe's compute hot-spot.

The paper's hot loop is the coordinate update: w·x_i, closed-form δ,
w += δ·x_i.  On TPU we re-block it for the memory hierarchy: rows are
tiled HBM→VMEM in blocks of B; within a block the updates run
*sequentially against a VMEM-resident w* (exact serial semantics — the
"maintain the primal" trick at VMEM latency); the sequential TPU grid
carries w across blocks, so a whole epoch is ONE pallas_call.

  dcd_block.py — the dense kernels (contiguous-tile + indexed/gather
                 modes, pl.pallas_call + BlockSpec)
  dcd_ell.py   — the sparse (ELL) indexed kernel: O(k_max) gather /
                 dummy-slot scatter per update, each row streamed from
                 HBM by DMA against a VMEM-resident primal (DESIGN.md §9),
                 and its sibling for packed ragged rows, which DMAs and
                 walks each row's own length, long rows in chunks
  dcd_feature.py — the 2D (data × model) feature-sharded block kernels:
                 per-shard partial (base, Gram) + δ-recursion/scatter
                 against a d₁_loc-word primal *shard*, one psum per
                 block instead of one per update (DESIGN.md §10)
  ops.py       — jitted wrappers with CPU interpret fallback, plus
                 ``dcd_block_update_pallas`` / ``dcd_ell_block_update_
                 pallas`` / ``dcd_feature_block_update_pallas`` — the
                 per-device block engines ``repro.core.sharded`` fuses
                 into its shard_map rounds (``use_kernel=True``) — and
                 the split-phase 2D entry points (``dcd_feature_gram_
                 pallas`` / ``dcd_feature_base_correction`` /
                 ``dcd_feature_update_pallas``) the double-buffered
                 round pipeline drives separately (DESIGN.md §11)
  ref.py       — pure-jnp oracle (identical update order)
"""

from repro.kernels.ops import (
    dcd_block_update_pallas,
    dcd_ell_block_update_pallas,
    dcd_epoch_pallas,
    dcd_feature_base_correction,
    dcd_feature_block_update_pallas,
    dcd_feature_gram_pallas,
    dcd_feature_update_pallas,
    dcd_ragged_block_update_pallas,
)
from repro.kernels.ref import dcd_epoch_ref

__all__ = [
    "dcd_block_update_pallas",
    "dcd_ell_block_update_pallas",
    "dcd_epoch_pallas",
    "dcd_epoch_ref",
    "dcd_feature_base_correction",
    "dcd_feature_block_update_pallas",
    "dcd_feature_gram_pallas",
    "dcd_feature_update_pallas",
    "dcd_ragged_block_update_pallas",
]
