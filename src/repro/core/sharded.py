"""Distributed PASSCoDe via ``shard_map`` — the TPU-native execution of
Algorithm 2 (DESIGN.md §2).

Mapping of the paper's shared-memory model onto an SPMD mesh:

  thread          → device along the ``data`` mesh axis
  shared w (DRAM) → per-device replica of w; devices run a *block* of B
                    locally-sequential DCD updates against their replica
                    (own updates immediately visible — the "maintain w"
                    trick), then exchange
  atomic adds     → ``jax.lax.psum`` of the per-device Δw each block
                    round: increments are never lost ⇒ **PASSCoDe-Atomic**
                    semantics with staleness τ ≤ B·(p−1) (Assumption 1)
  wild            → ``delay_rounds ≥ 1``: a device folds in the *previous*
                    round's psum while computing the current block —
                    modelling in-flight updates not yet visible.  Writes
                    stay lossless (a psum cannot drop increments), so this
                    is Atomic-with-larger-τ; true lost-write (LWW) physics
                    only exists on shared memory and is simulated in
                    ``repro.core.passcode`` instead.

α is sharded by rows (each device owns its block — disjoint coordinates,
like §3.3's per-thread permutation blocks); X rows likewise.  On a 1-D
``("data",)`` mesh w is replicated (d fits on-chip for rcv1/news20-scale
paper datasets).  On a 2-D ``("data", "model")`` mesh — the
webspam/kddb regime, where even the padded primal alone exceeds VMEM —
w and the feature dimension additionally shard along ``model``
(DESIGN.md §10): each device holds one ``FeatureShardedEll`` slice and
a d/m-word primal shard, the per-coordinate dot product psums its
partial over ``model`` (the mesh analogue of reading shared w under
atomic adds), and each device scatter-adds only its own shard — no
replicated primal exists anywhere.

The per-device block of B locally-sequential updates — the hot loop —
has eight interchangeable engines, selected by the mesh (1-D vs 2-D) ×
the type of ``X_host`` (dense array, ``repro.data.sparse.EllMatrix``, or
``CsrMatrix`` for rows of unequal length) × ``use_kernel`` (DESIGN.md
§6, §9, §10):

  * ``_local_block_update`` — unfused ``fori_loop`` of dense jnp ops;
  * ``_local_block_update_ell`` — unfused ELL engine: O(k_max) gather /
    dot / dummy-slot scatter per update against a (d+1)-padded primal;
  * ``_local_block_update_ragged`` — unfused engine over packed ragged
    rows (``pack_ragged``): each update walks its own row, 16 slots at a
    time, so the work follows the row's length, not the longest row's;
  * ``_local_block_update_feature`` — unfused 2-D engine: O(k_loc)
    local gather-dot, per-update psum of the partial wᵀx_i over
    ``model``, O(k_loc) scatter into this device's primal shard;
  * ``use_kernel=True`` — the fused Pallas indexed-block kernels
    (``repro.kernels.dcd_block_update_pallas`` dense,
    ``dcd_ell_block_update_pallas`` sparse,
    ``dcd_ragged_block_update_pallas`` packed ragged rows,
    ``dcd_feature_block_update_pallas`` 2-D — the latter batches the B
    per-update psums into one (base, Gram) psum per block): updates
    gather/scatter by row id inside the kernel (interpret mode on CPU,
    compiled on TPU); the dense shard and the 2-D slice are
    VMEM-resident, the 1-D ELL and ragged shards stay in HBM and each
    row is streamed in by DMA (a ragged row only the tiles it spans, in
    chunks when it is long).  ``"auto"`` fuses only on TPU when what the
    kernel holds fits VMEM — ``dcd_kernel_fits`` for the dense n_loc·d̃
    shard, ``dcd_ell_kernel_fits`` for the ELL kernel's 2·d₁ primal,
    ``dcd_feature_kernel_fits`` for the ~2·n_loc·k̃_loc + 2·d/m 2-D
    slice — falling back to pure jnp otherwise.

**Execution pipeline** (DESIGN.md §11): the whole multi-epoch solve is
ONE jitted dispatch (``make_sharded_pipeline`` /
``make_sharded_pipeline_2d``) — each device draws its own masked block
permutations *inside* the shard_map body from per-device PRNG keys
(``_device_block_perm``), every epoch and block round runs inside a
single ``lax.scan``, and duality gaps accumulate into a preallocated
on-device buffer honoring ``gap_every``.  On the 2-D
fused path with ``delay_rounds ≥ 1``, ``overlap`` additionally
double-buffers the block round (``_scan_rounds_overlap``): the
``model``-axis (base, Gram) psum of block t is carried in flight across
the round boundary and overlaps the gram kernel of block t+1, the base
staleness being repaired exactly by ``dcd_feature_base_correction``.

All engines compute the identical update sequence, and the serial
oracle replays it: ``repro.core.dcd.dcd_epoch`` fed the same per-device
draws, one round at a time (on one device, plain serial DCD in the
solver's order).  Tests assert agreement to atol 1e-5 across hinge /
squared-hinge / logistic and delay_rounds (``tests/test_sharded_kernel.
py``, ``tests/test_sharded_ell.py``, ``tests/test_sharded_feature.py``,
``tests/test_sharded_pipeline.py``).

Rows whose count is not divisible by the device count are no longer
dropped: the tail pads to p-divisibility with zero rows (q set to 1 so
δ stays finite) that are masked out of every block permutation, so they
are never selected where a device owns at least one real row, and can
never move w regardless (a zero row's rank-1 update is identically 0).
Likewise a block count that does not divide the device-local row count
rounds UP: the last block cycles through the valid prefix again rather
than silently skipping up to B−1 rows per device per epoch.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.objective import f32_dot
from repro.core.shrinking import active_mask_from_w
from repro.data.sparse import (
    CsrMatrix,
    EllMatrix,
    active_row_remap,
    dense_to_ell,
    ell_column_split,
    pack_ragged,
    pod_row_layout,
)
from repro.dist.compat import shard_map
from repro.dist.mesh import (
    adaptive_delay_policy,
    auto_mesh,
    data_axes,
    dcd_ell_kernel_fits,
    dcd_feature_kernel_fits,
    dcd_kernel_fits,
    dcd_ragged_kernel_fits,
    lane_pad,
    make_mesh,
    pipeline_overlap,
    pod_merge_policy,
    resolve_self_tuning,
    solver_mesh,
    solver_mesh_2d,
    solver_mesh_3d,
    solver_mesh_tasks,
    task_axis_policy,
    watchdog_trip,
)
from repro.dist.sharding import named, replicated
from repro.kernels.ops import (
    dcd_block_update_pallas,
    dcd_ell_block_update_pallas,
    dcd_feature_base_correction,
    dcd_feature_block_update_pallas,
    dcd_feature_gram_pallas,
    dcd_feature_update_pallas,
    dcd_ragged_block_update_pallas,
)
from repro.kernels.dcd_ell import (
    CHUNK_TILES,
    GRAIN,
    LANES,
    ragged_stream_rows,
    stream_rows,
)
from repro.kernels.gap_onehot import gap_onehot_fits, onehot_mv, onehot_rmv

# The solver's layers inside the compiled epoch, as ``jax.named_scope``s:
# each reaches the op_name of every instruction it holds, so a device
# trace can be reduced per layer (``_epoch_scan``).
SCOPE_PERM = "passcode.perm"
SCOPE_UPDATE = "passcode.update"
SCOPE_MERGE = "passcode.merge"
SCOPE_GAP = "passcode.gap"


class ShardedResult(NamedTuple):
    alpha: jnp.ndarray
    w_hat: jnp.ndarray
    gaps: jnp.ndarray
    rounds: int
    # live per-record metrics of the solve, aligned with ``gaps``:
    eps: jnp.ndarray  # ‖w(α) − ŵ‖, the perturbed-regularizer distance
    #   of core/backward_error.py (paper §4.2)
    active: jnp.ndarray  # active-set fraction (shrinking)
    delay: jnp.ndarray  # effective delay flag (adaptive)
    engine: str  # the block engine that ran (engine_name)
    gap_engine: str  # the gap's products (SolverSetup's)


def _local_block_update(X_loc, sq_loc, alpha_loc, w, idx_block, loss,
                        act=None, y=None):
    """B sequential DCD updates on this device's shard, locally-fresh w.
    ``act`` (optional (n_loc,) bool) freezes shrunk coordinates to
    zero-delta updates — the same gate as the serial reference's masked
    epoch.  ``y`` (optional (n_loc,) ±1 labels) folds each row on read —
    wᵀ(y_i·x_i) = y_i·wᵀx_i and the rank-1 update adds (δ·y_i)·x_i — so
    multi-task solves can share one unfolded X; ``y=None`` is the
    pre-folded binary convention, bit-identical to the historical
    engine."""

    def body(t, carry):
        alpha_loc, w_loc = carry
        i = idx_block[t]
        x = X_loc[i]
        wx = f32_dot(w_loc, x)
        if y is not None:
            wx = y[i] * wx
        delta = loss.delta(alpha_loc[i], wx, sq_loc[i])
        if act is not None:
            delta = jnp.where(act[i], delta, 0.0)
        dscale = delta if y is None else delta * y[i]
        return alpha_loc.at[i].add(delta), w_loc + dscale * x

    alpha_loc, w_new = jax.lax.fori_loop(
        0, idx_block.shape[0], body, (alpha_loc, w)
    )
    with jax.named_scope(SCOPE_MERGE):
        return alpha_loc, w_new - w  # (updated α shard, local Δw)


def _local_block_update_ell(cols_loc, vals_loc, sq_loc, alpha_loc, w_pad,
                            idx_block, loss, act=None, y=None):
    """B sequential DCD updates on this device's ELL shard: O(k_max)
    gather-dot and dummy-slot scatter per update.  ``w_pad`` carries the
    padded primal (slot d — and any lane padding above it — always 0,
    since padding ids scatter δ·0 there).  ``act`` freezes shrunk
    coordinates to zero-delta updates.  ``y`` folds rows on read like
    ``_local_block_update``."""

    def body(t, carry):
        alpha_loc, w_loc = carry
        i = idx_block[t]
        c = cols_loc[i]
        v = vals_loc[i]
        wx = jnp.sum(w_loc[c] * v)
        if y is not None:
            wx = y[i] * wx
        delta = loss.delta(alpha_loc[i], wx, sq_loc[i])
        if act is not None:
            delta = jnp.where(act[i], delta, 0.0)
        dscale = delta if y is None else delta * y[i]
        return alpha_loc.at[i].add(delta), w_loc.at[c].add(dscale * v)

    alpha_loc, w_new = jax.lax.fori_loop(
        0, idx_block.shape[0], body, (alpha_loc, w_pad)
    )
    with jax.named_scope(SCOPE_MERGE):
        return alpha_loc, w_new - w_pad  # (updated α shard, local Δw_pad)


def _local_block_update_ragged(rows_loc, sq_loc, alpha_loc, w_pad,
                               idx_block, loss, act=None, y=None):
    """``_local_block_update_ell`` over packed ragged rows
    (``pack_ragged``): ``rows_loc`` = (cols, vals, ptr, wid), the packed
    slots and each local row's first slot and slot count.  Each update
    walks its own row, ``GRAIN`` slots at a time, so the work follows
    the row's length, not the longest row's."""
    cols_loc, vals_loc, ptr_loc, wid_loc = rows_loc

    def body(t, carry):
        alpha_loc, w_loc = carry
        i = idx_block[t]
        p0, n_groups = ptr_loc[i], wid_loc[i] // GRAIN

        def group(g):
            at = (p0 + g * GRAIN,)
            return (jax.lax.dynamic_slice(cols_loc, at, (GRAIN,)),
                    jax.lax.dynamic_slice(vals_loc, at, (GRAIN,)))

        def dot(g, acc):
            c, v = group(g)
            return acc + jnp.sum(w_loc[c] * v)

        wx = jax.lax.fori_loop(0, n_groups, dot, jnp.float32(0.0))
        if y is not None:
            wx = y[i] * wx
        delta = loss.delta(alpha_loc[i], wx, sq_loc[i])
        if act is not None:
            delta = jnp.where(act[i], delta, 0.0)
        dscale = delta if y is None else delta * y[i]

        def axpy(g, w):
            c, v = group(g)
            return w.at[c].add(dscale * v)

        w_loc = jax.lax.fori_loop(0, n_groups, axpy, w_loc)
        return alpha_loc.at[i].add(delta), w_loc

    alpha_loc, w_new = jax.lax.fori_loop(
        0, idx_block.shape[0], body, (alpha_loc, w_pad)
    )
    with jax.named_scope(SCOPE_MERGE):
        return alpha_loc, w_new - w_pad  # (updated α shard, local Δw_pad)


def _local_block_update_feature(cols_loc, vals_loc, sq_loc, alpha_loc,
                                w_loc, idx_block, loss, act=None, y=None):
    """B sequential DCD updates on this device's (row-block × feature-
    shard) slice.  ``cols_loc``/``vals_loc`` hold *local* column ids
    into the (d_loc+1)-slot primal shard ``w_loc`` (per-shard dummy slot
    at d_loc); the full wᵀx_i is the psum over ``model`` of the O(k_loc)
    partial gather-dot — the mesh analogue of reading the paper's shared
    w — and the rank-1 update scatters only this shard.  ``sq_loc``
    carries the FULL row norms (summed over shards), so δ is identical
    on every feature shard and α stays replicated along ``model``.
    ``act`` freezes shrunk coordinates to zero-delta updates (the mask
    is replicated along ``model`` like α, so every shard gates
    identically).  ``y`` folds rows on read like
    ``_local_block_update`` — the psummed partial dot is y-free, so
    folding after the collective keeps every shard's δ identical."""

    def body(t, carry):
        alpha_loc, w_cur = carry
        i = idx_block[t]
        c = cols_loc[i]
        v = vals_loc[i]
        wx = jax.lax.psum(jnp.sum(w_cur[c] * v), "model")
        if y is not None:
            wx = y[i] * wx
        delta = loss.delta(alpha_loc[i], wx, sq_loc[i])
        if act is not None:
            delta = jnp.where(act[i], delta, 0.0)
        dscale = delta if y is None else delta * y[i]
        return alpha_loc.at[i].add(delta), w_cur.at[c].add(dscale * v)

    alpha_loc, w_new = jax.lax.fori_loop(
        0, idx_block.shape[0], body, (alpha_loc, w_loc)
    )
    with jax.named_scope(SCOPE_MERGE):
        return alpha_loc, w_new - w_loc  # (updated α shard, local Δw shard)


def _resolve_kernel_mode(use_kernel, n_loc: int, d: int, *,
                         ell: bool = False, block_size: int = 64,
                         ragged: bool = False):
    """Resolve ``use_kernel`` ∈ {False, True, "auto"} → (fused?, interpret?).

    "auto" fuses only where it pays: compiled on TPU with what the
    kernel holds resident fitting VMEM — the dense row shard
    (``dcd_kernel_fits``), or for an ELL shard (``ell``) the padded
    primal alone (``dcd_ell_kernel_fits``: the rows stream from HBM, so
    any n_loc is admitted), for packed ragged rows (``ragged``) that and
    the fixed SMEM row buffer (``dcd_ragged_kernel_fits``); everywhere
    else the pure-jnp block update is kept.  ``True`` forces the kernel
    — in interpret mode off-TPU, which validates semantics rather than
    speed.
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel == "auto":
        if ragged:
            use_kernel = on_tpu and dcd_ragged_kernel_fits(
                d, CHUNK_TILES, block_size=block_size)
        elif ell:
            use_kernel = on_tpu and dcd_ell_kernel_fits(
                d, block_size=block_size)
        else:
            use_kernel = on_tpu and dcd_kernel_fits(n_loc, d)
    return bool(use_kernel), not on_tpu


def _resolve_kernel_mode_feature(use_kernel, n_loc: int, k_loc: int,
                                 d_loc: int, block_size: int):
    """``_resolve_kernel_mode`` for the 2-D path: "auto" consults
    ``dcd_feature_kernel_fits`` — the ~2·n_loc·k̃_loc + 2·d/m policy
    that admits webspam/kddb-scale d where both 1-D policies reject."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel == "auto":
        use_kernel = on_tpu and dcd_feature_kernel_fits(
            n_loc, k_loc, d_loc, block_size=block_size
        )
    return bool(use_kernel), not on_tpu


def _n_blocks(n_loc: int, block_size: int) -> int:
    """Blocks per device per epoch — rounded UP so an epoch is a full
    pass.  The old ``n_loc // block_size`` floor silently skipped up to
    B−1 rows per device per epoch whenever ``block_size ∤ n_loc``; the
    masked-permutation machinery already cycles the valid prefix, so the
    tail block simply revisits early rows instead."""
    return max(-(-n_loc // block_size), 1)


def _device_block_perm(sub, my, p: int, n_loc: int, n_rows: int,
                       n_blocks: int, block_size: int):
    """One device's masked block permutation for one epoch — the draw
    that never selects padding rows, runnable *inside* the shard_map
    body from the epoch subkey and this device's ``data``-axis index
    ``my``.

    Device ``my`` owns local rows [0, n_loc) = global [my·n_loc,
    (my+1)·n_loc); only the first ``v = clip(n_rows − my·n_loc, 1,
    n_loc)`` are real data.  The device draws a permutation of n_loc,
    stable-sorts the invalid ids to the back (keeping the permuted
    order of the valid ones) and cycles through the valid prefix — with
    no padding this reduces exactly to ``permutation(n_loc)[:n_blocks·
    B]``.  The clip to ≥1 covers a device that owns *only* padding
    (possible when n_rows < (p−1)·n_loc): it repeatedly selects local
    row 0, a zero row with q←1 whose update cannot move w.

    Returns (n_blocks, B).  The serial oracle of the tests replays this
    draw per device and epoch, so the solve and the oracle run one
    update sequence (``tests/test_sharded_pipeline.py``)."""
    v = jnp.clip(n_rows - my * n_loc, 1, n_loc)
    return _device_block_perm_v(sub, my, p, n_loc, v, n_blocks,
                                block_size)


def _device_block_perm_v(sub, my, p: int, n_loc: int, v, n_blocks: int,
                         block_size: int):
    """``_device_block_perm`` with the valid-row count ``v`` passed in
    directly instead of derived from a global row prefix — the shared
    draw core.  The pod solver needs this because its validity is
    per-pod (each pod carries its own padded tail, so validity is not
    one global prefix): a device at (pod k, data my) passes the
    flattened fleet index ``k·p + my`` into ``p = n_pods·p_data`` split
    keys and its pod-local valid count, keeping the whole fleet on ONE
    key chain (the serial oracle ``repro.core.cocoa.cocoa_pod_solve``
    replays the same chain on the host, which is what makes
    pod-vs-oracle agreement bit-structural).  DESIGN.md §13."""
    m = n_blocks * block_size
    keys = jax.random.split(sub, p)
    perm = jax.random.permutation(keys[my], n_loc)
    order = jnp.argsort(perm >= v)  # stable: valid ids first, in order
    sel = perm[order][jnp.arange(m) % v]
    return sel.reshape(n_blocks, block_size)


def _device_block_perm_masked(sub, my, p: int, n_loc: int, n_blocks: int,
                              block_size: int, act, rp):
    """``_device_block_perm`` drawing over an arbitrary *active* row set
    instead of the valid prefix — the repacked epoch's draw (DESIGN.md
    §12).

    ``act`` is this device's (n_loc,) bool active mask (already ANDed
    with row validity).  ``active_row_remap`` compacts the active rows
    to the front (stable, fixed shape); the draw then permutes
    ``[0, count)`` through the same key chain, maps back through the
    remap ids, and lays the result over the n_blocks·B slots.  Rounds
    past ``ceil(count/B)`` blocks are skipped by the dyn round scan, so
    a mostly-frozen shard's epoch gets *shorter*, not just cheaper per
    update.

    Tail slots (≥ count) depend on the runtime repack flag ``rp``:

      * ``rp`` False — cycle the drawn sequence, exactly like
        ``_device_block_perm`` cycles the valid prefix.  With ``act``
        equal to the valid-prefix mask the whole draw then reduces
        *bit-exactly* to the plain one (the remap ids are the identity
        because validity is a prefix), which is why the shrinking
        pipeline can route every epoch through this draw and still
        bit-match the plain solver whenever repack is not in effect.
      * ``rp`` True — point at an *inactive* row instead (act-gated to
        an exact zero-delta no-op), so each active row is updated
        exactly once per repacked epoch.  Cycling here would re-update
        the support-vector rows — the mutually correlated ones — a
        second time per round across all p devices simultaneously, and
        that synchronized overshoot measurably diverges at p ≥ 4.  A
        fully-active shard (no inactive row to point at) falls back to
        cycling, which is the plain schedule again."""
    m = n_blocks * block_size
    keys = jax.random.split(sub, p)
    ids, cnt = active_row_remap(act)
    v = jnp.maximum(cnt, 1)  # all-frozen shard: one (gated) no-op row
    perm = jax.random.permutation(keys[my], n_loc)
    order = jnp.argsort(perm >= v)  # stable: sub-perm of [0, v) first
    pos = jnp.arange(m)
    cyc = perm[order][pos % v]  # slot j < v: j-th drawn row, distinct
    n_inact = n_loc - cnt  # remap ids [cnt:] — the act-gated no-ops
    noop = cnt + (pos % jnp.maximum(n_inact, 1))
    fill = jnp.where(rp & (n_inact > 0), noop, cyc)
    sel = ids[jnp.where(pos < v, cyc, fill)]
    return sel.reshape(n_blocks, block_size)


def _scan_rounds(block_update, alpha_loc, w_loc, dw_prev, blocks_loc,
                 delay_rounds: int):
    """The round structure every engine shares, run inside a shard_map
    body: per round the device's block update runs against the
    (possibly stale) effective w, Δw is psummed over ``data`` — the
    whole primal on a 1-D mesh, this device's feature shard on a 2-D
    mesh — and either applied now (atomic) or deferred one round
    (``delay_rounds`` staleness).  ``block_update(alpha_loc, w_eff,
    idx_block)`` closes over the device's data shard."""

    def one_round(carry, idx_block):
        alpha_loc, w_loc, dw_prev = carry
        if delay_rounds > 0:
            # fold in last round's aggregate only now (stale view)
            with jax.named_scope(SCOPE_MERGE):
                w_eff = w_loc + dw_prev
        else:
            w_eff = w_loc
        with jax.named_scope(SCOPE_UPDATE):
            alpha_loc, dw_local = block_update(alpha_loc, w_eff,
                                               idx_block)
        with jax.named_scope(SCOPE_MERGE):
            dw_all = jax.lax.psum(dw_local, "data")
            if delay_rounds > 0:
                # defer applying this round's aggregate to next round
                return (alpha_loc, w_loc + dw_prev, dw_all), ()
            return (alpha_loc, w_loc + dw_all, dw_prev), ()

    (alpha_loc, w_loc, dw_prev), _ = jax.lax.scan(
        one_round, (alpha_loc, w_loc, dw_prev), blocks_loc
    )
    return alpha_loc, w_loc, dw_prev


def _scan_rounds_dyn(block_update, alpha_loc, w_loc, dw_prev, dw_own,
                     blocks_loc, act, n_run, delay_flag):
    """The self-tuning round scan (DESIGN.md §12): ``_scan_rounds`` with
    (a) the active mask ``act`` gating every δ, (b) rounds past
    ``n_run`` — the repacked block count, uniform across devices via
    pmax — ``cond``-skipped, collectives included, and (c) the delayed
    mode promoted to a *runtime* flag with real stale reads, so the
    gap-trend controller can trade staleness for convergence mid-solve.

    Unlike the static delayed branch of ``_scan_rounds`` (whose carry
    discipline is exact bookkeeping that lets the psum overlap the next
    round on TPU), the dyn delayed mode implements the §2 τ table
    literally: while ``delay_flag`` is set, a round's psum stays in
    flight for one round and the *next* round's update reads a w that
    has this device's own last-round updates (``dw_own`` — shared-memory
    visibility, exactly PASSCoDe's model) but not its peers', so
    τ ≈ 2·B·(p−1).  At p = 1 ``dw_own == dw_prev`` and the delayed
    schedule is bit-identical to the synchronous one — the serial
    identity every equivalence test leans on.  A delayed→synchronous
    switch folds the in-flight aggregate on its first round; the caller
    always flushes ``w + dw_prev`` at the end (dw_prev is 0 when the
    solve ended synchronous)."""
    delay_on = jnp.asarray(delay_flag, jnp.int32) > 0

    def one_round(carry, xs):
        idx_block, r = xs

        def run(c):
            alpha_loc, w_loc, dw_prev, dw_own = c
            # delayed: peers' last-round aggregate is still in flight —
            # read own last-round updates only (stale by one psum)
            with jax.named_scope(SCOPE_MERGE):
                w_eff = w_loc + jnp.where(delay_on, dw_own, dw_prev)
            with jax.named_scope(SCOPE_UPDATE):
                alpha_n, dw_local = block_update(alpha_loc, w_eff,
                                                 idx_block, act)
            with jax.named_scope(SCOPE_MERGE):
                dw_all = jax.lax.psum(dw_local, "data")
                # last round's aggregate lands now; this round's is
                # applied eagerly (sync) or kept in flight (delayed)
                w_new = w_loc + dw_prev + jnp.where(
                    delay_on, jnp.zeros_like(dw_all), dw_all)
                dw_new = jnp.where(delay_on, dw_all,
                                   jnp.zeros_like(dw_all))
                dwo_new = jnp.where(delay_on, dw_local,
                                    jnp.zeros_like(dw_local))
            return alpha_n, w_new, dw_new, dwo_new

        carry = jax.lax.cond(r < n_run, run, lambda c: c, carry)
        return carry, ()

    (alpha_loc, w_loc, dw_prev, dw_own), _ = jax.lax.scan(
        one_round, (alpha_loc, w_loc, dw_prev, dw_own),
        (blocks_loc, jnp.arange(blocks_loc.shape[0])),
    )
    return alpha_loc, w_loc, dw_prev, dw_own


def _overlap_round_fns(cols_loc, vals_loc, sq_loc, loss, interpret):
    """The three split phases of the fused 2-D block round, bound to this
    device's resident slice (``repro.kernels.ops`` entry points)."""

    def gram_fn(w_ref, idx):
        return dcd_feature_gram_pallas(cols_loc, vals_loc, w_ref, idx,
                                       interpret=interpret)

    def corr_fn(dvec, idx):
        return dcd_feature_base_correction(cols_loc, vals_loc, dvec, idx)

    def update_fn(alpha_loc, w_ref, idx, base, gram, act=None, y=None):
        return dcd_feature_update_pallas(cols_loc, vals_loc, sq_loc,
                                         alpha_loc, w_ref, idx, base,
                                         gram, loss=loss,
                                         interpret=interpret, active=act,
                                         y=y)

    return gram_fn, corr_fn, update_fn


def _scan_rounds_overlap(gram_fn, corr_fn, update_fn, alpha_loc, w_loc,
                         dw_prev, blocks_loc, inflight, next0, act=None):
    """``_scan_rounds`` for the fused 2-D engine with the block round
    double-buffered (DESIGN.md §11): the ``model``-axis (base, Gram)
    psum of block t is *carried in flight across the round boundary* and
    overlaps the gram kernel of block t+1 instead of being consumed
    between that block's own gram and update kernels.

    Invariant: entering round t the carry holds the already-psummed
    ``(base⁰_t, gram_t)`` of block t, whose base was computed against
    W_t — the local primal shard *without* the round's in-flight
    data-axis aggregate D_t (= round t−1's psum).  The Gram never
    depends on w, and the base is repaired exactly:

        base_t = base⁰_t + psum_model(D_t ᵀ x)   (= (W_t + D_t)ᵀx,
                                                  the effective w)

    so only the cheap O(B·k̃_loc) correction and its (B,) psum wait for
    the aggregates, while the O(B²·k̃_loc) gram kernel of block t+1 and
    its (B + B²)-word psum run against the already-known W_{t+1} =
    W_t + D_t.  The bookkeeping is exactly the delayed branch of
    ``_scan_rounds`` (requires ``delay_rounds ≥ 1``; the caller flushes
    the final aggregate), and the update sequence is identical to the
    eager engines in exact arithmetic — tests pin agreement at atol
    1e-5.

    The in-flight aggregate is now explicit state: the caller passes
    the psummed (base⁰, Gram) of ``blocks_loc[0]`` (referenced to the
    entering ``w_loc``) and the first block ``next0`` of the *following*
    round sequence, and gets the aggregate issued for ``next0`` back in
    the return.  The pipelined epoch scan threads it across epoch
    boundaries — each epoch peeks the next epoch's first block through
    the deterministic PRNG chain — so the prologue gram that used to be
    recomputed (and one gram wasted on a wrapped dummy block) every
    epoch is paid once per *solve* instead (the carry out of the final
    epoch is the only discard).  ``act`` gates shrunk coordinates in the
    update kernel (the gram needs no mask — a frozen row's δ = 0
    contributes nothing through the recursion or the scatter).
    """
    nxt = jnp.concatenate([blocks_loc[1:], next0[None]], axis=0)

    def one_round(carry, blk):
        idx, idx_next = blk
        alpha_loc, w_loc, dw_prev, (base0, gram) = carry
        with jax.named_scope(SCOPE_MERGE):
            w_next = w_loc + dw_prev  # W_{t+1}: known before D_{t+1} lands
        with jax.named_scope(SCOPE_UPDATE):
            # issue block t+1's gram/base⁰ + model psum — independent of
            # the in-flight (base⁰_t, gram_t) psum and of this round's
            # data psum, so both collectives can hide behind it
            inflight_n = gram_fn(w_next, idx_next)
            # repair block t's stale base, consuming the in-flight
            # aggregate
            base = base0 + corr_fn(dw_prev, idx)
            alpha_loc, w_upd = update_fn(alpha_loc, w_next, idx, base,
                                         gram, act)
        with jax.named_scope(SCOPE_MERGE):
            dw_all = jax.lax.psum(w_upd - w_next, "data")
        return (alpha_loc, w_next, dw_all, inflight_n), ()

    (alpha_loc, w_loc, dw_prev, inflight), _ = jax.lax.scan(
        one_round, (alpha_loc, w_loc, dw_prev, inflight),
        (blocks_loc, nxt),
    )
    return alpha_loc, w_loc, dw_prev, inflight


# ------------------------------------------------ on-device gap path ----


def _gap_slots(epochs: int, gap_every: int) -> int:
    """How many duality gaps the solve records — every ``gap_every``-th
    epoch plus the final one."""
    gap_every = max(int(gap_every), 1)
    return sum(1 for e in range(epochs)
               if (e + 1) % gap_every == 0 or e == epochs - 1)


def _gap_onehot(use_k: bool, sparse: bool, d_run: int) -> bool:
    """Whether the 1-D gap's two products run as the one-hot MXU pass
    (``repro.kernels.gap_onehot``, DESIGN.md §17): wherever the fused
    block engine runs a sparse shard and the primal is narrow enough
    for the pass to beat XLA's scatter and gather (``gap_onehot_fits``);
    those XLA ops everywhere else."""
    return bool(use_k and sparse and gap_onehot_fits(d_run))


def _make_gap_1d(loss, X_loc, ell: bool, axes=("data",), onehot=False,
                 interpret=False):
    """Per-device duality-gap contribution for the pipelined 1-D solve:
    gap(α) = ‖w(α)‖² + Σ_i [ℓ(w(α)ᵀx_i) + ℓ*(−α_i)] computed from the
    padded shards — padding rows are masked out of both sums and
    contribute zero columns to w(α), so the value matches
    ``duality_gap(alpha[:n], X, loss)`` up to reduction order.
    Alongside the gap it returns the live backward-error metric
    ‖w(α) − ŵ‖ against the maintained primal view ``w_view`` — the
    perturbed-regularizer distance of ``core/backward_error.py`` (paper
    §4.2, ε = w̄ − ŵ): w(α) is already formed for the gap, so the
    metric is one extra d-length difference, no extra collectives.
    The whole computation — psums included — is ``cond``-gated on
    ``rec``: the predicate is a function of the scanned epoch index
    only, so it is uniform across devices and skipped epochs are
    collective-free (no d-sized all-reduce of zeros).

    ``axes`` names the row-reduction axes — ``("data",)`` on a plain
    mesh, ``("pod", "data")`` on a pod mesh, where w(α) and the loss
    sums reduce over the whole fleet while ``w_view`` is the pod's
    (possibly stale) read view, making the recorded backward error the
    pod-staleness distance (DESIGN.md §13).

    With ``onehot`` (``_gap_onehot``) an ELL shard's two products run
    as the one-hot MXU pass, in interpret mode with ``interpret``."""
    if ell:
        cols_loc, vals_loc = X_loc
    if ell and onehot:
        def rmv(am, d_run):
            return onehot_rmv(cols_loc, vals_loc, d_run, am,
                              interpret=interpret)

        def mv(wa):
            return onehot_mv(cols_loc, vals_loc, wa, row_sum=True,
                             interpret=interpret)
    elif ell:
        def rmv(am, d_run):
            return jnp.zeros((d_run,), jnp.float32).at[cols_loc].add(
                am[:, None] * vals_loc)

        def mv(wa):
            return jnp.sum(wa[cols_loc] * vals_loc, axis=1)
    else:
        def rmv(am, d_run):
            return f32_dot(X_loc.T, am)

        def mv(wa):
            return f32_dot(X_loc, wa)

    return _gap_1d_from(loss, rmv, mv, axes)


def _make_gap_ragged(loss, X_loc, axes=("data",), onehot=False,
                     interpret=False):
    """``_make_gap_1d`` over packed ragged rows (X_loc = (cols, vals,
    gseg, ptr, wid), ``pack_ragged``): both products read the packed
    slots once — w(α) scatters the α of each slot's row, and the margins
    sum each group of ``GRAIN`` slots and add the groups into their rows
    (``gseg``, sorted) — so the work follows the true nonzeros, not the
    longest row.  With ``onehot`` the scatter and the gather of the
    slots, viewed as (slots / 128, 128), run as the one-hot MXU pass."""
    cols_loc, vals_loc, gseg_loc, ptr_loc, _ = X_loc
    n_loc = ptr_loc.shape[0]
    if onehot:
        c_view, v_view = (cols_loc.reshape(-1, LANES),
                          vals_loc.reshape(-1, LANES))

        def slot_rmv(ag, d_run):  # each group's α scales its slots
            return onehot_rmv(c_view, v_view, d_run,
                              ag.reshape(-1, LANES // GRAIN),
                              interpret=interpret)

        def slot_mv(wa):
            return onehot_mv(c_view, v_view, wa, row_sum=False,
                             interpret=interpret)
    else:
        def slot_rmv(ag, d_run):
            contrib = ag[:, None] * vals_loc.reshape(-1, GRAIN)
            return jnp.zeros((d_run,), jnp.float32).at[cols_loc].add(
                contrib.reshape(-1))

        def slot_mv(wa):
            return wa[cols_loc] * vals_loc

    def rmv(am, d_run):
        return slot_rmv(am[gseg_loc], d_run)

    def mv(wa):
        part = jnp.sum(slot_mv(wa).reshape(-1, GRAIN), axis=1)
        return jax.ops.segment_sum(part, gseg_loc, n_loc,
                                   indices_are_sorted=True)

    return _gap_1d_from(loss, rmv, mv, axes)


def _gap_1d_from(loss, rmv, mv, axes):
    """The 1-D gap and backward error over a shard's products ``rmv``
    (Xᵀa into a d_run-word primal) and ``mv`` (X·w)."""

    def gap(rec, alpha_loc, mask, d_run, w_view, y=None):
        am = jnp.where(mask, alpha_loc, 0.0)

        def compute(args):
            am, w_view = args
            # multi-task (unfolded X): w(α) = Σ α_i·y_i·x_i and the
            # primal margin is y_i·wᵀx_i, while ℓ*(−α) reads raw α —
            # the exact folded-row algebra, applied on read
            ay = am if y is None else am * y
            wa = jax.lax.psum(rmv(ay, d_run), axes)  # w(α), replicated
            z = mv(wa)
            if y is not None:
                z = y * z
            s = jnp.sum(jnp.where(
                mask, loss.primal_loss(z) + loss.conj(am), 0.0))
            g = f32_dot(wa, wa) + jax.lax.psum(s, axes)
            e = wa - w_view  # dummy/pad slots are 0 in both
            return g, jnp.sqrt(f32_dot(e, e))

        return jax.lax.cond(
            rec, compute,
            lambda a: (jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)),
            (am, w_view))

    return gap


def _make_gap_2d(loss, cols_loc, vals_loc, d1_loc: int, axes=("data",)):
    """``_make_gap_1d`` for the 2-D mesh: w(α) stays sharded along
    ``model`` (each device scatters its local slice and psums over
    ``data`` — over ``("pod", "data")`` on a pod mesh), the per-row dot
    psums over ``model``, ‖w(α)‖² over ``model`` — no replicated primal
    is ever formed, matching the solve's own memory model.  The
    backward-error metric ‖w(α) − ŵ‖ likewise reduces shard-local
    squared distances over ``model``."""

    def gap(rec, alpha_loc, mask, w_view, y=None):
        am = jnp.where(mask, alpha_loc, 0.0)

        def rmv(a):
            return jnp.zeros((d1_loc,), jnp.float32).at[cols_loc].add(
                a[:, None] * vals_loc)

        def compute(args):
            am, w_view = args
            ay = am if y is None else am * y  # fold on read (multi-task)
            wa = jax.lax.psum(rmv(ay), axes)  # this shard's w(α) slice
            z = jax.lax.psum(jnp.sum(wa[cols_loc] * vals_loc, axis=1),
                             "model")
            if y is not None:
                z = y * z
            s = jnp.sum(jnp.where(
                mask, loss.primal_loss(z) + loss.conj(am), 0.0))
            g = (jax.lax.psum(f32_dot(wa, wa), "model")
                 + jax.lax.psum(s, axes))
            e = wa - w_view  # dummy slots are 0 in both
            return g, jnp.sqrt(jax.lax.psum(f32_dot(e, e), "model"))

        return jax.lax.cond(
            rec, compute,
            lambda a: (jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)),
            (am, w_view))

    return gap


def _make_shrink_1d(loss, X_loc, ell: bool, shrink_tol: float, valid):
    """Per-device active-mask recompute for the pipelined 1-D solve:
    fresh projected gradients from the carried (α, effective w) —
    wᵀx_i via the shard's own matvec — through the serial reference's
    ``active_mask`` rule, ANDed with row validity so padding rows never
    count as active."""
    if ell:
        cols_loc, vals_loc = X_loc

        def mv(wv):
            return jnp.sum(wv[cols_loc] * vals_loc, axis=1)
    else:
        def mv(wv):
            return f32_dot(X_loc, wv)

    def mask_fn(alpha_loc, w_view, y=None):
        wx = mv(w_view)
        if y is not None:
            wx = y * wx  # fold on read (multi-task unfolded X)
        return active_mask_from_w(loss, alpha_loc, wx,
                                  shrink_tol) & valid

    return mask_fn


def _make_shrink_2d(loss, cols_loc, vals_loc, shrink_tol: float, valid):
    """``_make_shrink_1d`` for the 2-D mesh: the full wᵀx_i psums the
    shard-local partial dots over ``model`` (the same collective shape
    as the solve's own per-update read), so the mask — like α — comes
    out replicated along ``model``."""

    def mask_fn(alpha_loc, w_view, y=None):
        wx = jax.lax.psum(
            jnp.sum(w_view[cols_loc] * vals_loc, axis=1), "model")
        if y is not None:
            wx = y * wx  # fold on read (multi-task unfolded X)
        return active_mask_from_w(loss, alpha_loc, wx, shrink_tol) & valid

    return mask_fn


# ------------------------------------------------------ epoch builders ----


def _block_update_1d(loss, use_kernel: bool, interpret: bool, ell: bool,
                     ragged: bool = False):
    """The per-device block engine for a 1-D mesh, shared by the
    per-epoch and pipelined builders: ``(view, block_update)``.
    ``view(X_loc)`` is what the engine reads, made once per dispatch
    outside the round loop — the fused ELL kernel streams each row from
    a lane-aligned copy of the shard (``stream_rows``) and walks the
    shard's own k_max slots; the ragged kernel views the packed slots
    as lane tiles (``ragged_stream_rows``) and walks each row's own
    slots; every other engine reads X as placed.
    ``act`` (optional (n_loc,) mask) freezes shrunk coordinates —
    forwarded to the fused kernels as the f32 active operand, to the jnp
    engines as the bool gate."""

    def view(X_loc):
        if ragged:
            cols_loc, vals_loc, _, ptr_loc, wid_loc = X_loc
            if not use_kernel:
                return cols_loc, vals_loc, ptr_loc, wid_loc
            with jax.named_scope(SCOPE_UPDATE):
                return (ragged_stream_rows(cols_loc, vals_loc), ptr_loc,
                        wid_loc)
        if not (ell and use_kernel):
            return X_loc
        cols_loc, vals_loc = X_loc
        with jax.named_scope(SCOPE_UPDATE):
            return stream_rows(cols_loc, vals_loc), cols_loc.shape[1]

    def block_update(X_eng, sq_loc, alpha_loc, w_eff, idx_block,
                     act=None, y=None):
        if ragged and use_kernel:
            rows, ptr_loc, wid_loc = X_eng
            return dcd_ragged_block_update_pallas(
                rows, ptr_loc, wid_loc, sq_loc, alpha_loc, w_eff,
                idx_block, loss=loss, interpret=interpret, active=act, y=y,
            )
        if ragged:
            return _local_block_update_ragged(
                X_eng, sq_loc, alpha_loc, w_eff, idx_block, loss, act=act,
                y=y,
            )
        if ell and use_kernel:
            rows, k = X_eng
            return dcd_ell_block_update_pallas(
                rows, sq_loc, alpha_loc, w_eff, idx_block, k=k, loss=loss,
                interpret=interpret, active=act, y=y,
            )
        if ell:
            cols_loc, vals_loc = X_eng
            return _local_block_update_ell(
                cols_loc, vals_loc, sq_loc, alpha_loc, w_eff, idx_block,
                loss, act=act, y=y,
            )
        if use_kernel:
            return dcd_block_update_pallas(
                X_eng, sq_loc, alpha_loc, w_eff, idx_block, loss=loss,
                interpret=interpret, active=act, y=y,
            )
        return _local_block_update(
            X_eng, sq_loc, alpha_loc, w_eff, idx_block, loss, act=act,
            y=y,
        )

    return view, block_update


def _block_update_2d(loss, use_kernel: bool, interpret: bool):
    """The per-device block engine for a 2-D mesh (eager composition;
    the overlapped round drives the split phases directly).  ``act``
    freezes shrunk coordinates like the 1-D engine."""

    def block_update(cols_loc, vals_loc, sq_loc, alpha_loc, w_eff,
                     idx_block, act=None, y=None):
        if use_kernel:
            return dcd_feature_block_update_pallas(
                cols_loc, vals_loc, sq_loc, alpha_loc, w_eff, idx_block,
                loss=loss, interpret=interpret, active=act, y=y,
            )
        return _local_block_update_feature(
            cols_loc, vals_loc, sq_loc, alpha_loc, w_eff, idx_block,
            loss, act=act, y=y,
        )

    return block_update


# --------------------------------------------------- pipeline builders ----


def _epoch_scan(rounds, gap, carry, draw_perm, *, epochs: int,
                total_epochs: int, e0, n_gaps: int, gap_every: int,
                record: bool, n_blocks: int, valid=None, shrink=None,
                adaptive: bool = False, adaptive_ratio: float = 0.95,
                delay0: int = 0, overlap: bool = False, pod=None,
                watchdog=None, fault=None):
    """The epoch loop every pipelined device body runs: split the PRNG
    chain (``key, sub = split(key)`` per epoch), draw this device's
    masked block permutation, run the round scan, and ``cond``-record
    the duality gap (plus the live backward-error, active-fraction and
    delay-flag metrics) into preallocated buffers.  Shared by the 1-D and 2-D
    builders so the PRNG chain and the metric schedule cannot diverge
    between them.  Its layers carry the operator-facing names
    ``passcode.perm`` (the draw), ``passcode.update`` (the block engine),
    ``passcode.merge`` (the round's Δw, psum and fold) and
    ``passcode.gap`` (the gap and its records) as ``jax.named_scope``s.

    The self-tuning extensions (DESIGN.md §12) are all optional and
    compile away when unused:

      ``shrink = (mask_fn, every, repack_threshold|None, n_rows, B)``
        carries an active mask in the scan state, recomputed on-device
        every ``every`` epochs from the carried (α, effective w) and
        passed into the round scan so frozen coordinates take exact
        zero-delta updates.  The final epoch always runs unshrunk over
        the full valid set (LIBLINEAR's final full pass), so the solve
        never returns with a wrongly-frozen coordinate.  With a repack
        threshold, epochs whose *global* active fraction drops below it
        redraw their blocks over the compacted active set
        (``_device_block_perm_masked``) and ``cond``-skip the rounds
        past ceil(max-device-count/B) — shorter epochs, not just
        cheaper updates.  The fraction is psummed and the run count
        pmaxed, so both are uniform across devices and the skipped
        rounds' collectives stay collective-free.

      ``adaptive`` carries the effective delay flag and the last
        recorded gap: at every record the gap-trend controller
        (``repro.dist.mesh.adaptive_delay_policy``) decides whether the
        *next* epochs may stay delayed (gap still improving) or must go
        synchronous (stalling) — staleness is traded for convergence
        mid-solve, inside the scan.  The back-off is a one-way latch
        (seed with ``delay_rounds=1`` to start async): once dropped,
        asynchrony stays dropped.  ``adaptive_ratio`` is the
        controller's improvement threshold: the default 0.95 only backs
        off on a hard stall, while stricter values (e.g. 0.5 — "keep
        async only while the gap halves per record") anneal the solve
        async→synchronous as it nears the optimum, where stale reads
        cost proportionally the most.  With shrinking on, the same stall
        signal trips a *sticky* repack guard: repacked epochs
        concentrate the active set into fewer psum intervals (effective
        τ × 1/frac), so once the gap stalls the solve falls back to
        full-length epochs for good.

      ``overlap`` (the overlapped 2-D round) threads the in-flight
        (base, Gram) aggregate — ``carry["inflight"]`` — across epoch
        boundaries: each epoch
        peeks the *next* epoch's first block through the deterministic
        PRNG chain (``_, sub_next = split(key)`` is exactly what the
        next iteration's draw will consume) and hands the round scan
        its follow-on target, so the per-epoch prologue gram of the old
        schedule is paid once per solve.

      ``pod = (n_pods, pod_delay_rounds)`` turns each epoch into a
        Hybrid-DCA outer round (DESIGN.md §13): the pod-local pipelined
        epoch runs from a shared (α, w) snapshot, its inner in-flight
        carry is flushed into the pod's primal delta, and the pods'
        deltas merge as a CoCoA β_K=1 average — α rescaled locally by
        1/n_pods, w bumped by the pod-mean Δw — through a length-
        ``pod_delay_rounds`` FIFO.  The aggregate issued at outer round
        t lands at t+pod_delay_rounds, a bounded-staleness model of a
        slow cross-pod (DCN) allreduce; ``pod_delay_rounds=0`` is a
        synchronous CoCoA outer round.  With ``adaptive`` the delay
        latch acts at the *pod* level: on a gap stall the whole FIFO
        drains and merges stay synchronous for good.  The recorded
        backward error is taken against the stale read view, so eps is
        exactly the in-flight merge mass — the perturbed-regularizer
        quantity of Table 2.

    Segmentation (DESIGN.md §14): the caller hands in the FULL carried
    state (``carry``, built by ``_fresh_carry`` or restored from a
    checkpoint) and gets the full carried state back — the scan runs
    ``epochs`` iterations with *global* epoch indices ``e0..e0+epochs``
    against a ``total_epochs``-long schedule, so the record slots, the
    final-epoch unshrunk pass, and any armed fault all key on the
    global epoch and a segmented run replays the uninterrupted one
    bit-for-bit.  ``watchdog = (bad_fn, blowup, floor)`` adds the
    sticky ``health``/``gph``/``eph`` trio; ``fault`` is the compiled
    ``(nan_e, drop_e, dup_e)`` chaos triple (−1 = off)."""
    shrink_on = shrink is not None
    if shrink_on:
        mask_fn, shrink_every, repack_thresh, n_rows, blk = shrink
        shrink_every = max(int(shrink_every), 1)
    pod_on = pod is not None
    if pod_on:
        n_pods, pod_delay = pod
        pod_scale = 1.0 / n_pods
    dyn = (shrink_on or adaptive) and not overlap and not pod_on
    if fault is not None:
        nan_e, drop_e, dup_e = fault

    def epoch_body(carry, e):
        c = dict(carry)
        with jax.named_scope(SCOPE_PERM):
            key, sub = jax.random.split(c["key"])
        c["key"] = key
        final = e == total_epochs - 1
        if shrink_on:
            w_view = c["w"] + c["dw"]

            def recompute(st):
                act, frac, nrun, rp = st
                m = mask_fn(c["alpha"], w_view)
                cnt = jnp.sum(m.astype(jnp.int32))
                frac = (jax.lax.psum(cnt, "data").astype(jnp.float32)
                        / n_rows)
                if repack_thresh is not None:
                    rp = frac < repack_thresh
                    # ceil of the largest per-device active count —
                    # pmaxed so every device runs the same round count
                    nrun = jnp.clip(
                        -(-jax.lax.pmax(cnt, "data") // blk),
                        1, n_blocks).astype(jnp.int32)
                return m, frac, nrun, rp

            c["act"], c["frac"], c["nrun"], c["rp"] = jax.lax.cond(
                e % shrink_every == 0, recompute, lambda st: st,
                (c["act"], c["frac"], c["nrun"], c["rp"]))
            # final epoch: full unshrunk pass (recovers any wrongly-
            # frozen coordinate, LIBLINEAR semantics)
            act_run = jnp.where(final, valid, c["act"])
            use_rp = c["rp"] & jnp.logical_not(final)
            if adaptive:
                # the controller's stall signal also guards repack:
                # concentrating the active set into fewer rounds raises
                # the effective staleness τ by ~1/frac, and on problems
                # near the Liu–Wright boundary that alone can diverge —
                # once the gap stalls, repacking stays off (sticky; the
                # cheap rounds are not worth a stalled solve)
                use_rp = use_rp & (c["rpok"] > 0)
            act_draw = jnp.where(use_rp, c["act"], valid)
            n_run_e = jnp.where(use_rp, c["nrun"], jnp.int32(n_blocks))
            with jax.named_scope(SCOPE_PERM):
                blocks_loc = draw_perm(sub, act_draw, use_rp)
        else:
            act_run = None
            n_run_e = jnp.int32(n_blocks)
            with jax.named_scope(SCOPE_PERM):
                blocks_loc = draw_perm(sub)
        delay_flag = c["delay"] if adaptive else jnp.int32(delay0)
        if pod_on:
            a0, w0 = c["alpha"], c["w"]
            a1, w1, dwi = rounds(a0, w0, jnp.zeros_like(w0), blocks_loc)
            dw_pod = (w1 + dwi) - w0
            c["alpha"] = a0 + pod_scale * (a1 - a0)
            g_m = pod_scale * jax.lax.psum(dw_pod, "pod")
            if fault is not None:
                # declarative chaos (DESIGN.md §14): poison/drop/dup THIS
                # outer round's cross-pod merge — -1 compiles each away
                if nan_e >= 0:
                    g_m = g_m + jnp.where(e == nan_e,
                                          jnp.float32(jnp.nan),
                                          jnp.float32(0.0))
                if drop_e >= 0:
                    g_m = g_m * jnp.where(e == drop_e, jnp.float32(0.0),
                                          jnp.float32(1.0))
                if dup_e >= 0:
                    g_m = g_m * jnp.where(e == dup_e, jnp.float32(2.0),
                                          jnp.float32(1.0))
            if pod_delay == 0:
                c["w"] = w0 + g_m
            else:
                buf = c["pbuf"]
                w_async = w0 + buf[0]
                pbuf_async = jnp.concatenate([buf[1:], g_m[None]], 0)
                if adaptive:
                    # pod-level anneal latch: once the gap-trend
                    # controller drops asynchrony, drain the whole
                    # FIFO and merge synchronously from then on
                    sync = delay_flag == 0
                    c["w"] = jnp.where(sync, w0 + buf.sum(0) + g_m,
                                       w_async)
                    c["pbuf"] = jnp.where(sync, jnp.zeros_like(buf),
                                          pbuf_async)
                else:
                    c["w"] = w_async
                    c["pbuf"] = pbuf_async
        elif overlap:
            # peek the next epoch's first block: the next iteration
            # splits the carried key exactly like this
            with jax.named_scope(SCOPE_PERM):
                _, sub_next = jax.random.split(key)
                next0 = (draw_perm(sub_next, valid, False) if shrink_on
                         else draw_perm(sub_next))[0]
            (c["alpha"], c["w"], c["dw"], c["inflight"]) = rounds(
                c["alpha"], c["w"], c["dw"], blocks_loc, c["inflight"],
                next0, act_run)
        elif dyn:
            c["alpha"], c["w"], c["dw"], c["dwo"] = rounds(
                c["alpha"], c["w"], c["dw"], c["dwo"], blocks_loc,
                act_run, n_run_e, delay_flag)
        else:
            c["alpha"], c["w"], c["dw"] = rounds(
                c["alpha"], c["w"], c["dw"], blocks_loc)
        if fault is not None and not pod_on and nan_e >= 0:
            # single-pod chaos: poison the primal at epoch nan_e —
            # models a corrupted "data"/"model" psum reaching w
            c["w"] = c["w"] + jnp.where(e == nan_e, jnp.float32(jnp.nan),
                                        jnp.float32(0.0))
        if record:
            with jax.named_scope(SCOPE_GAP):
                rec = ((e + 1) % gap_every == 0) | final
                w_view = c["w"] + c["dw"]
                g, eps = gap(rec, c["alpha"], w_view)
                slot = c["slot"]
                c["gaps"] = jnp.where(rec, c["gaps"].at[slot].set(g),
                                      c["gaps"])
                c["epsb"] = jnp.where(rec, c["epsb"].at[slot].set(eps),
                                      c["epsb"])
                fr = c["frac"] if shrink_on else jnp.float32(1.0)
                c["actb"] = jnp.where(rec, c["actb"].at[slot].set(fr),
                                      c["actb"])
                c["delayb"] = jnp.where(
                    rec,
                    c["delayb"].at[slot].set(delay_flag.astype(jnp.float32)),
                    c["delayb"])
                if adaptive:
                    # gap-trend controller: improving ⇒ stay async,
                    # stalling ⇒ go synchronous (both vs the last record)
                    new_flag = adaptive_delay_policy(
                        c["gapprev"], g, improve_ratio=adaptive_ratio)
                    # one-way latch: the controller only ever *backs off*
                    # asynchrony (seed with delay_rounds=1 to start async).
                    # Re-raising oscillates — a synchronous epoch converges
                    # fast, which reads as "async affordable", whose stale
                    # epoch converges slowly, which reads as "back off" —
                    # and each flip re-pays the staleness tax exactly where
                    # it is most expensive (near the optimum)
                    c["delay"] = jnp.where(
                        rec, jnp.minimum(delay_flag, new_flag), delay_flag)
                    if shrink_on:
                        # the repack guard keys on a *hard* stall (the 0.95
                        # default), not the annealing threshold: a gap that
                        # merely stops halving is normal near the optimum,
                        # while a gap that stops moving under repack is the
                        # τ-concentration signature the guard exists for
                        stall = adaptive_delay_policy(c["gapprev"], g)
                        c["rpok"] = jnp.where(rec, c["rpok"] * stall,
                                              c["rpok"])
                    c["gapprev"] = jnp.where(rec, g, c["gapprev"])
                if watchdog is not None:
                    # on-device divergence watchdog (DESIGN.md §14): a
                    # NaN/Inf census of (α, ŵ) plus the gap/eps trend test,
                    # folded into a sticky per-segment health code.  The
                    # healthy-baseline pair only advances on clean records,
                    # so a blow-up is judged against the last good state.
                    bad_fn, wd_blowup, wd_floor = watchdog
                    nb = jax.lax.cond(
                        rec, lambda a: bad_fn(*a),
                        lambda a: jnp.int32(0), (c["alpha"], w_view))
                    code = watchdog_trip(c["gph"], g, c["eph"], eps, nb,
                                         blowup=wd_blowup, floor=wd_floor)
                    ok = rec & (code == 0)
                    c["health"] = jnp.where(
                        rec, jnp.maximum(c["health"], code), c["health"])
                    c["gph"] = jnp.where(ok, g, c["gph"])
                    c["eph"] = jnp.where(ok, eps, c["eph"])
                c["slot"] = slot + rec.astype(jnp.int32)
        return c, ()

    out, _ = jax.lax.scan(epoch_body, carry,
                          jnp.arange(epochs, dtype=jnp.int32) + e0)
    return out


def pipeline_state_keys(*, dyn: bool, shrink_on: bool, adaptive: bool,
                        pod_fifo: int, watchdog: bool):
    """The key set of the pipelined solver's carried-state dict
    (``SolverState``, DESIGN.md §14) for a given knob combination.
    This IS the checkpoint schema: the segmented solver persists exactly
    these leaves, and resume validates against them.  ``inflight`` is
    deliberately absent — the overlapped 2-D aggregate is a pure
    function of the carried (w, key) and is reconstructed at segment
    entry (see the builder prologue)."""
    keys = ["alpha", "w", "dw", "key", "gaps", "epsb", "actb", "delayb",
            "slot", "epoch"]
    if dyn:
        keys.append("dwo")
    if shrink_on:
        keys += ["act", "frac", "nrun", "rp"]
    if adaptive:
        keys += ["delay", "gapprev"]
        if shrink_on:
            keys.append("rpok")
    if pod_fifo:
        keys.append("pbuf")
    if watchdog:
        keys += ["health", "gph", "eph"]
    return keys


def _fresh_carry(alpha_loc, w_loc, dw_prev, key, n_gaps, *, n_blocks,
                 dyn, shrink_on, adaptive, delay0, pod_fifo=0,
                 watchdog=False, n_tasks=0):
    """Epoch-0 carried state for ``_epoch_scan`` — the one place the
    scan state's initial values live, shared by the legacy whole-solve
    entry points and ``init_pipeline_state``.  The shrink ``act`` mask
    is NOT seeded here: inside a shard_map body the caller seeds it
    from its device-local ``valid`` (the global-state path seeds the
    global mask instead).

    ``n_tasks > 0`` is the multi-task layout (DESIGN.md §16): the
    caller hands in (α, w, dw, key) already stacked with a leading
    (K,) task axis, and every *other* leaf — record buffers, slot,
    per-task self-tuning latches — is tiled here, EXCEPT ``epoch``,
    which stays an unbatched shared scalar: the epoch counter drives
    the scan's ``xs`` and the record/shrink/final predicates, which
    must stay uniform across tasks so ``lax.cond`` stays a cond (not a
    select) under the task vmap and skipped epochs stay
    collective-free."""
    K = int(n_tasks)
    t = ((lambda x: jnp.broadcast_to(x, (K,) + x.shape)) if K
         else (lambda x: x))
    carry = {"alpha": alpha_loc, "w": w_loc, "dw": dw_prev, "key": key,
             "gaps": t(jnp.zeros((n_gaps,), jnp.float32)),
             "epsb": t(jnp.zeros((n_gaps,), jnp.float32)),
             "actb": t(jnp.zeros((n_gaps,), jnp.float32)),
             "delayb": t(jnp.zeros((n_gaps,), jnp.float32)),
             "slot": t(jnp.int32(0)), "epoch": jnp.int32(0)}
    if dyn:
        # the dyn delayed mode's own-updates view (real stale reads);
        # w_loc is already (K, d_run) on the multi-task layout
        carry["dwo"] = jnp.zeros_like(w_loc)
    if shrink_on:
        carry["frac"] = t(jnp.float32(1.0))
        carry["nrun"] = t(jnp.int32(n_blocks))
        carry["rp"] = t(jnp.zeros((), bool))
    if adaptive:
        carry["delay"] = t(jnp.int32(delay0))
        carry["gapprev"] = t(jnp.float32(jnp.inf))
        if shrink_on:
            carry["rpok"] = t(jnp.int32(1))  # sticky repack guard
    if pod_fifo:
        # (K, fifo, d_run) multi-task / (fifo, d_run) binary — the FIFO
        # axis sits next to the primal so per-task views keep buf[0]
        carry["pbuf"] = jnp.zeros(
            w_loc.shape[:-1] + (pod_fifo,) + w_loc.shape[-1:],
            w_loc.dtype)
    if watchdog:
        carry["health"] = t(jnp.int32(0))
        carry["gph"] = t(jnp.float32(jnp.inf))
        carry["eph"] = t(jnp.float32(jnp.inf))
    return carry


def _make_badcount(axes, two_d: bool):
    """Watchdog NaN/Inf census, run only on record epochs: count
    non-finite entries of the dual shard (psummed over the row axes so
    every device sees the global count) and of the primal view (psummed
    over ``model`` on the 2-D mesh; replicated on 1-D)."""

    def bad(alpha_loc, w_view):
        ba = jax.lax.psum(
            jnp.sum((~jnp.isfinite(alpha_loc)).astype(jnp.int32)), axes)
        bw = jnp.sum((~jnp.isfinite(w_view)).astype(jnp.int32))
        if two_d:
            bw = jax.lax.psum(bw, "model")
        return ba + bw

    return bad


def _check_pipeline_chaos(*, record, watchdog, fault, pod_on):
    """Shared builder-argument validation for the resilience knobs."""
    if watchdog and not record:
        raise ValueError(
            "watchdog=True requires record=True: the divergence test "
            "keys on the recorded gap/eps schedule (DESIGN.md §14)")
    if fault is None:
        return None
    fault = tuple(int(v) for v in fault)
    if len(fault) != 3:
        raise ValueError("fault must be (nan_epoch, drop_epoch, "
                         "dup_epoch), -1 disabling each")
    if not pod_on and (fault[1] >= 0 or fault[2] >= 0):
        raise ValueError(
            "drop/dup merge faults target the cross-pod merge and need "
            "a pod mesh; on a single-pod mesh only the NaN-psum fault "
            "is meaningful")
    return fault


def make_sharded_pipeline(mesh: Mesh, loss, *, epochs: int,
                          block_size: int, n_blocks: int, n_rows: int,
                          delay_rounds: int = 0, use_kernel: bool = False,
                          interpret: bool | None = None, ell: bool = False,
                          ragged: bool = False,
                          record: bool = True, gap_every: int = 1,
                          shrink_every: int = 0, shrink_tol: float = 1e-3,
                          repack_threshold: float | None = None,
                          adaptive: bool = False,
                          adaptive_ratio: float = 0.95,
                          pod_delay_rounds: int = 0,
                          total_epochs: int | None = None,
                          segmented: bool = False,
                          watchdog: bool = False,
                          watchdog_blowup: float = 4.0,
                          watchdog_floor: float = 1e-3,
                          fault=None):
    """Build the single-dispatch multi-epoch solver for a 1-D
    ``("data",)`` mesh (DESIGN.md §11): per-epoch PRNG block draws,
    every block round, and duality-gap recording all run inside one
    jitted ``lax.scan`` over epochs — no per-epoch host dispatch, no
    per-epoch ``device_put`` of permutations, no host sync before the
    solve returns.

    Each device splits the carried PRNG key (``key, sub = split(key)``
    per epoch) and draws its own masked block permutation from ``sub``
    and its ``data``-axis index (``_device_block_perm``), so the serial
    oracle that replays those draws runs the identical update sequence
    (``tests/test_sharded_pipeline.py``).  Gaps land
    in a preallocated (n_gaps,) on-device buffer honoring ``gap_every``
    — the whole gap computation, collectives included, is
    ``cond``-gated to recorded epochs (the predicate is uniform across
    devices), so skipped epochs are collective-free.

    Self-tuning knobs (DESIGN.md §12): ``shrink_every ≥ 1`` recomputes
    an on-device active mask from the carried (α, effective w) every
    that many epochs and freezes shrunk coordinates to zero-delta
    updates (final epoch always unshrunk — LIBLINEAR's recovery pass);
    ``repack_threshold`` additionally redraws blocks over the compacted
    active set and skips the now-empty tail rounds once the global
    active fraction drops below it; ``adaptive`` lets the gap-trend
    controller back the delayed-psum flag off (one-way latch) at every
    record (``delay_rounds`` seeds the flag, ``adaptive_ratio`` the
    improvement threshold).  Validate combinations with
    ``repro.dist.mesh.resolve_self_tuning`` before calling.

    On a mesh carrying a ``pod`` axis the builder raises the epoch loop
    to the Hybrid-DCA outer round (DESIGN.md §13): rows shard jointly
    over ``("pod", "data")``, every round psum stays pod-local (the
    named ``"data"`` axis only reduces its own mesh dimension), and
    each epoch ends in the CoCoA β_K=1 cross-pod merge, delayed by
    ``pod_delay_rounds`` (validate with ``repro.dist.mesh.
    pod_merge_policy`` before calling; ``adaptive`` then latches the
    *pod* FIFO, not the inner delayed psum).

    ``ragged`` takes X as the five row-sharded arrays of packed ragged
    rows (``pack_ragged``: cols, vals, gseg, ptr, wid) in place of the
    ELL pair; the engine and the gap then read each row's own slots.

    Returns ``fn(X, sq_norms, alpha, w, key, carry_dw) → (alpha, w,
    carry_dw, gaps, eps, active, delay)``; with ``delay_rounds > 0`` (or
    any self-tuning mode, or ``pod_delay_rounds > 0``) the caller
    flushes the final in-flight aggregate (``w + carry_dw``).

    Resilience mode (DESIGN.md §14): with ``segmented=True`` the built
    function is instead ``fn(X, sq_norms, st) → st`` over the full
    ``SolverState`` dict (``pipeline_state_keys``), running ``epochs``
    epochs of a ``total_epochs``-long schedule starting at
    ``st["epoch"]`` — chained segments replay the whole-solve dispatch
    bit-for-bit.  ``watchdog=True`` adds the sticky on-device health
    code; ``fault`` compiles a ``(nan_e, drop_e, dup_e)`` chaos triple
    into the scan."""
    axis = "data"
    p = mesh.shape["data"]
    pod_on = "pod" in mesh.axis_names
    pods = mesh.shape["pod"] if pod_on else 1
    n_pod_loc = -(-n_rows // pods)
    row_ax = ("pod", "data") if pod_on else axis
    gap_axes = ("pod", "data") if pod_on else ("data",)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    gap_every = max(int(gap_every), 1)
    total = int(total_epochs) if total_epochs is not None else int(epochs)
    n_gaps = _gap_slots(total, gap_every) if record else 0
    shrink_on = shrink_every > 0
    dyn = (shrink_on or adaptive) and not pod_on
    fault = _check_pipeline_chaos(record=record, watchdog=watchdog,
                                  fault=fault, pod_on=pod_on)
    view, block_update = _block_update_1d(loss, use_kernel, interpret, ell,
                                          ragged)
    x_spec = ((P(row_ax),) * 5 if ragged
              else (P(row_ax), P(row_ax)) if ell else P(row_ax))
    delay0 = int(pod_delay_rounds > 0) if pod_on else delay_rounds
    pod_fifo = pod_delay_rounds if (pod_on and pod_delay_rounds > 0) else 0

    def device_body(X_loc, sq_loc, st, y_loc=None):
        my = jax.lax.axis_index(axis)
        X_eng = view(X_loc)
        n_loc = st["alpha"].shape[-1]
        d_run = st["w"].shape[-1]
        if pod_on:
            kp = jax.lax.axis_index("pod")
            npv = jnp.clip(n_rows - kp * n_pod_loc, 0, n_pod_loc)
        else:
            npv = n_rows
        valid = jnp.arange(n_loc) < (npv - my * n_loc)

        def draw(sub, act=None, rp=False):
            if act is None:
                if pod_on:
                    v = jnp.clip(npv - my * n_loc, 1, n_loc)
                    return _device_block_perm_v(
                        sub, kp * p + my, pods * p, n_loc, v,
                        n_blocks, block_size)
                return _device_block_perm(sub, my, p, n_loc, n_rows,
                                          n_blocks, block_size)
            return _device_block_perm_masked(sub, my, p, n_loc,
                                             n_blocks, block_size,
                                             act, rp)

        # one task's whole epoch scan, with its (n_loc,) label row bound
        # into every closure that reads X (DESIGN.md §16).  Binary calls
        # it once with y=None — bit-identical to the pre-task-axis body;
        # multi-task vmaps it over the leading (K,) axis of every state
        # leaf EXCEPT the epoch counter, which was popped off above so
        # the scan xs and the record/shrink/final predicates stay
        # unbatched (conds stay conds under the vmap).
        def run_task(carry, y):
            if record:
                onehot = _gap_onehot(use_kernel, ell or ragged, d_run)
                gap_fn = (_make_gap_ragged(loss, X_loc, axes=gap_axes,
                                           onehot=onehot,
                                           interpret=interpret)
                          if ragged else
                          _make_gap_1d(loss, X_loc, ell, axes=gap_axes,
                                       onehot=onehot, interpret=interpret))
                gap = lambda rec, a, wv: gap_fn(rec, a, valid, d_run,
                                                wv, y)
            else:
                gap = None
            bu = lambda a, w_eff, idx, act=None: block_update(
                X_eng, sq_loc, a, w_eff, idx, act, y)
            if dyn:
                rounds = functools.partial(_scan_rounds_dyn, bu)
            else:
                rounds = functools.partial(_scan_rounds, bu,
                                           delay_rounds=delay_rounds)
            shrink = None
            if shrink_on:
                mfn = _make_shrink_1d(loss, X_loc, ell, shrink_tol,
                                      valid)
                shrink = ((lambda a, wv: mfn(a, wv, y)),
                          shrink_every, repack_threshold, n_rows,
                          block_size)
                if "act" not in carry:
                    carry = dict(carry)
                    carry["act"] = valid
            return _epoch_scan(rounds, gap, carry, draw, epochs=epochs,
                               total_epochs=total, e0=e0, n_gaps=n_gaps,
                               gap_every=gap_every, record=record,
                               n_blocks=n_blocks, valid=valid,
                               shrink=shrink, adaptive=adaptive,
                               adaptive_ratio=adaptive_ratio,
                               delay0=delay0,
                               pod=((pods, pod_delay_rounds)
                                    if pod_on else None),
                               watchdog=((_make_badcount(gap_axes,
                                                         False),
                                          watchdog_blowup,
                                          watchdog_floor)
                                         if watchdog else None),
                               fault=fault)

        carry = dict(st)
        e0 = carry.pop("epoch")
        out = (run_task(carry, None) if y_loc is None
               else jax.vmap(run_task)(carry, y_loc))
        out["epoch"] = e0 + jnp.int32(epochs)
        return out

    tax = "task" if "task" in mesh.axis_names else None

    if segmented:
        def st_spec(k, multitask):
            if not multitask:
                return P(row_ax) if k in ("alpha", "act") else P()
            if k == "epoch":
                return P()  # shared scalar — drives the scan xs
            if k in ("alpha", "act"):
                return P(tax, row_ax)
            if k == "pbuf":
                return P(tax, None)
            return P(tax)

        def solve_seg(X, sq_norms, st, y=None):
            st_specs = {k: st_spec(k, y is not None) for k in st}
            if y is None:
                return shard_map(
                    device_body,
                    mesh=mesh,
                    in_specs=(x_spec, P(row_ax), st_specs),
                    out_specs=st_specs,
                    check_vma=False,
                )(X, sq_norms, st)
            return shard_map(
                device_body,
                mesh=mesh,
                in_specs=(x_spec, P(row_ax), st_specs, P(tax, row_ax)),
                out_specs=st_specs,
                check_vma=False,
            )(X, sq_norms, st, y)

        return jax.jit(solve_seg)

    def solve(X, sq_norms, alpha, w, key, carry_dw, y=None):
        def device_fn(X_loc, sq_loc, alpha_loc, w_rep, key, dw_prev,
                      y_loc=None):
            st = _fresh_carry(alpha_loc, w_rep, dw_prev, key, n_gaps,
                              n_blocks=n_blocks, dyn=dyn,
                              shrink_on=shrink_on, adaptive=adaptive,
                              delay0=delay0, pod_fifo=pod_fifo,
                              watchdog=watchdog,
                              n_tasks=(alpha_loc.shape[0]
                                       if y_loc is not None else 0))
            out = device_body(X_loc, sq_loc, st, y_loc)
            dw_out = out["pbuf"].sum(-2) if pod_fifo else out["dw"]
            return (out["alpha"], out["w"], dw_out, out["gaps"],
                    out["epsb"], out["actb"], out["delayb"])

        if y is None:
            return shard_map(
                device_fn,
                mesh=mesh,
                in_specs=(x_spec, P(row_ax), P(row_ax), P(), P(), P()),
                out_specs=(P(row_ax), P(), P(), P(), P(), P(), P()),
                check_vma=False,  # carries flip replicated→varying
            )(X, sq_norms, alpha, w, key, carry_dw)
        return shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(x_spec, P(row_ax), P(tax, row_ax), P(tax), P(tax),
                      P(tax), P(tax, row_ax)),
            out_specs=(P(tax, row_ax), P(tax), P(tax), P(tax), P(tax),
                      P(tax), P(tax)),
            check_vma=False,  # carries flip replicated→varying
        )(X, sq_norms, alpha, w, key, carry_dw, y)

    return jax.jit(solve)


def make_sharded_pipeline_2d(mesh: Mesh, loss, *, epochs: int,
                             block_size: int, n_blocks: int, n_rows: int,
                             delay_rounds: int = 0,
                             use_kernel: bool = False,
                             interpret: bool | None = None,
                             record: bool = True, gap_every: int = 1,
                             overlap: bool | str = False,
                             shrink_every: int = 0,
                             shrink_tol: float = 1e-3,
                             repack_threshold: float | None = None,
                             adaptive: bool = False,
                             adaptive_ratio: float = 0.95,
                             pod_delay_rounds: int = 0,
                             total_epochs: int | None = None,
                             segmented: bool = False,
                             watchdog: bool = False,
                             watchdog_blowup: float = 4.0,
                             watchdog_floor: float = 1e-3,
                             fault=None):
    """``make_sharded_pipeline`` for the 2-D ``("data", "model")`` mesh:
    the whole multi-epoch feature-sharded solve in one dispatch, with
    the same in-body per-device block draws (keyed on the ``data``-axis
    index only, so every feature shard of a data block runs the same
    sequence) and a ``model``-aware on-device gap (``_make_gap_2d`` —
    w(α) never leaves its shards).  ``overlap`` double-buffers the
    fused block round (``_scan_rounds_overlap``; needs ``use_kernel``
    and ``delay_rounds ≥ 1``) — with the in-flight (base, Gram)
    aggregate now carried *across epoch boundaries* through the epoch
    scan, so only one prologue gram is paid per solve.  The self-tuning
    knobs mirror the 1-D builder (shrinking composes with ``overlap``;
    repack and the adaptive controller need the dyn round scan and are
    rejected alongside it by ``resolve_self_tuning``).  On a mesh
    carrying a ``pod`` axis the same Hybrid-DCA outer round as the 1-D
    builder applies (DESIGN.md §13): rows over ``("pod", "data")``,
    pod-local ``data``/``model`` collectives, per-epoch cross-pod
    merge of the per-shard primal slices delayed by
    ``pod_delay_rounds``.

    The resilience knobs (``segmented``/``total_epochs``/``watchdog``/
    ``fault``) mirror the 1-D builder (DESIGN.md §14).  The overlapped
    in-flight (base, Gram) aggregate is NOT part of the segmented
    state: the carry out of any epoch is ``gram_fn(w, next-epoch first
    block)``, a pure function of the carried (w, key), so segment entry
    recomputes it bit-exactly — fresh and resumed solves share the one
    prologue code path."""
    p = mesh.shape["data"]
    pod_on = "pod" in mesh.axis_names
    pods = mesh.shape["pod"] if pod_on else 1
    n_pod_loc = -(-n_rows // pods)
    row_ax = ("pod", "data") if pod_on else "data"
    gap_axes = ("pod", "data") if pod_on else ("data",)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    overlap = pipeline_overlap(overlap, two_d=True, fused=use_kernel,
                               delay_rounds=delay_rounds)
    gap_every = max(int(gap_every), 1)
    total = int(total_epochs) if total_epochs is not None else int(epochs)
    n_gaps = _gap_slots(total, gap_every) if record else 0
    shrink_on = shrink_every > 0
    dyn = (shrink_on or adaptive) and not overlap and not pod_on
    fault = _check_pipeline_chaos(record=record, watchdog=watchdog,
                                  fault=fault, pod_on=pod_on)
    block_update = _block_update_2d(loss, use_kernel, interpret)
    delay0 = int(pod_delay_rounds > 0) if pod_on else delay_rounds
    pod_fifo = pod_delay_rounds if (pod_on and pod_delay_rounds > 0) else 0

    def device_body(cols4, vals4, sq_loc, st, y_loc=None):
        cols_loc = cols4[:, 0]  # (n_loc, 1, k) → (n_loc, k)
        vals_loc = vals4[:, 0]
        my = jax.lax.axis_index("data")
        n_loc = st["alpha"].shape[-1]
        d1_run = st["w"].shape[-1]
        if pod_on:
            kp = jax.lax.axis_index("pod")
            npv = jnp.clip(n_rows - kp * n_pod_loc, 0, n_pod_loc)
        else:
            npv = n_rows
        valid = jnp.arange(n_loc) < (npv - my * n_loc)

        def draw(sub, act=None, rp=False):
            if act is None:
                if pod_on:
                    v = jnp.clip(npv - my * n_loc, 1, n_loc)
                    return _device_block_perm_v(
                        sub, kp * p + my, pods * p, n_loc, v,
                        n_blocks, block_size)
                return _device_block_perm(sub, my, p, n_loc, n_rows,
                                          n_blocks, block_size)
            return _device_block_perm_masked(sub, my, p, n_loc,
                                             n_blocks, block_size,
                                             act, rp)

        # per-task epoch scan (see the 1-D builder): binary runs it once
        # with y=None, multi-task vmaps it over the leading (K,) state
        # axis with the shared epoch counter popped off beforehand.  The
        # overlap prologue lives INSIDE so each task's in-flight (base,
        # Gram) aggregate follows its own PRNG chain.
        def run_task(carry, y):
            if record:
                gap_fn = _make_gap_2d(loss, cols_loc, vals_loc,
                                      d1_run, axes=gap_axes)
                gap = lambda rec, a, wv: gap_fn(rec, a, valid, wv, y)
            else:
                gap = None
            if shrink_on and "act" not in carry:
                carry = dict(carry)
                carry["act"] = valid
            if overlap:
                gram_fn, corr_fn, update_fn = _overlap_round_fns(
                    cols_loc, vals_loc, sq_loc, loss, interpret)
                ufn = lambda a, w_ref, idx, base, gram, act=None: (
                    update_fn(a, w_ref, idx, base, gram, act, y))
                rounds = functools.partial(_scan_rounds_overlap,
                                           gram_fn, corr_fn, ufn)
                # prologue: the NEXT epoch's first block, referenced to
                # the entering primal shard — the split below is exactly
                # what the first scan iteration will consume, so a fresh
                # solve pays its one up-front gram here and a RESUMED
                # segment reconstructs the carried-out in-flight
                # aggregate of the previous segment bit-exactly (it
                # never hits the disk).  The Gram is label-free, so the
                # multi-task prologue needs no fold.
                _, sub0 = jax.random.split(carry["key"])
                b0 = (draw(sub0, valid) if shrink_on else draw(sub0))[0]
                carry = dict(carry)
                carry["inflight"] = gram_fn(carry["w"], b0)
            else:
                bu = lambda a, w_eff, idx, act=None: block_update(
                    cols_loc, vals_loc, sq_loc, a, w_eff, idx, act, y)
                if dyn:
                    rounds = functools.partial(_scan_rounds_dyn, bu)
                else:
                    rounds = functools.partial(_scan_rounds, bu,
                                               delay_rounds=delay_rounds)
            shrink = None
            if shrink_on:
                mfn = _make_shrink_2d(loss, cols_loc, vals_loc,
                                      shrink_tol, valid)
                shrink = ((lambda a, wv: mfn(a, wv, y)),
                          shrink_every, repack_threshold, n_rows,
                          block_size)
            out = _epoch_scan(rounds, gap, carry, draw, epochs=epochs,
                              total_epochs=total, e0=e0, n_gaps=n_gaps,
                              gap_every=gap_every, record=record,
                              n_blocks=n_blocks, valid=valid,
                              shrink=shrink, adaptive=adaptive,
                              adaptive_ratio=adaptive_ratio,
                              delay0=delay0, overlap=overlap,
                              pod=((pods, pod_delay_rounds)
                                   if pod_on else None),
                              watchdog=((_make_badcount(gap_axes, True),
                                         watchdog_blowup,
                                         watchdog_floor)
                                        if watchdog else None),
                              fault=fault)
            out.pop("inflight", None)
            return out

        carry = dict(st)
        e0 = carry.pop("epoch")
        out = (run_task(carry, None) if y_loc is None
               else jax.vmap(run_task)(carry, y_loc))
        out["epoch"] = e0 + jnp.int32(epochs)
        return out

    tax = "task" if "task" in mesh.axis_names else None

    if segmented:
        def spec_of(k, multitask):
            if not multitask:
                if k in ("alpha", "act"):
                    return P(row_ax)
                if k in ("w", "dw", "dwo"):
                    return P("model")
                if k == "pbuf":
                    return P(None, "model")
                return P()
            if k == "epoch":
                return P()  # shared scalar — drives the scan xs
            if k in ("alpha", "act"):
                return P(tax, row_ax)
            if k in ("w", "dw", "dwo"):
                return P(tax, "model")
            if k == "pbuf":
                return P(tax, None, "model")
            return P(tax)

        def solve_seg(X, sq_norms, st, y=None):
            st_specs = {k: spec_of(k, y is not None) for k in st}
            cols, vals = X
            if y is None:
                return shard_map(
                    device_body,
                    mesh=mesh,
                    in_specs=(P(row_ax, "model"), P(row_ax, "model"),
                              P(row_ax), st_specs),
                    out_specs=st_specs,
                    check_vma=False,
                )(cols, vals, sq_norms, st)
            return shard_map(
                device_body,
                mesh=mesh,
                in_specs=(P(row_ax, "model"), P(row_ax, "model"),
                          P(row_ax), st_specs, P(tax, row_ax)),
                out_specs=st_specs,
                check_vma=False,
            )(cols, vals, sq_norms, st, y)

        return jax.jit(solve_seg)

    def solve(X, sq_norms, alpha, w, key, carry_dw, y=None):
        def device_fn(cols4, vals4, sq_loc, alpha_loc, w_loc, key,
                      dw_prev, y_loc=None):
            st = _fresh_carry(alpha_loc, w_loc, dw_prev, key, n_gaps,
                              n_blocks=n_blocks, dyn=dyn,
                              shrink_on=shrink_on, adaptive=adaptive,
                              delay0=delay0, pod_fifo=pod_fifo,
                              watchdog=watchdog,
                              n_tasks=(alpha_loc.shape[0]
                                       if y_loc is not None else 0))
            out = device_body(cols4, vals4, sq_loc, st, y_loc)
            dw_out = out["pbuf"].sum(-2) if pod_fifo else out["dw"]
            return (out["alpha"], out["w"], dw_out, out["gaps"],
                    out["epsb"], out["actb"], out["delayb"])

        cols, vals = X
        if y is None:
            return shard_map(
                device_fn,
                mesh=mesh,
                in_specs=(P(row_ax, "model"), P(row_ax, "model"),
                          P(row_ax), P(row_ax), P("model"), P(),
                          P("model")),
                out_specs=(P(row_ax), P("model"), P("model"), P(), P(),
                           P(), P()),
                check_vma=False,  # carries flip replicated→varying
            )(cols, vals, sq_norms, alpha, w, key, carry_dw)
        return shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(P(row_ax, "model"), P(row_ax, "model"), P(row_ax),
                      P(tax, row_ax), P(tax, "model"), P(tax),
                      P(tax, "model"), P(tax, row_ax)),
            out_specs=(P(tax, row_ax), P(tax, "model"), P(tax, "model"),
                       P(tax), P(tax), P(tax), P(tax)),
            check_vma=False,  # carries flip replicated→varying
        )(cols, vals, sq_norms, alpha, w, key, carry_dw, y)

    return jax.jit(solve)


class RowLayout(NamedTuple):
    """Counters of packed ragged rows (``pack_ragged``), set once at
    ``prepare_solver``: the true nonzeros, the slots one pass over every
    row walks (each row rounded up to ``GRAIN``), the distinct row
    widths, and the rows too long for one SMEM chunk of the streamed
    kernel (streamed in chunks)."""

    nnz: int
    slots_walked: int
    buckets: int
    chunked_rows: int


class SolverSetup(NamedTuple):
    """The resolved-and-placed half of a solve (DESIGN.md §14): mesh +
    admission policies + the padded, device-resident dataset — i.e.
    everything ``sharded_passcode_solve`` needs besides the (α, w)
    iterates themselves.  Built once by ``prepare_solver`` and shared
    by the whole-solve entry point and the segmented resilience layer
    (``repro.resilience``), which builds per-segment pipelines —
    possibly with degraded knobs — against the same arrays."""

    mesh: Mesh
    loss: object
    two_d: bool
    pod_on: bool
    pods: int
    p: int
    m: int
    n: int
    d: int
    n_loc: int
    n_pad: int
    n_blocks: int
    block_size: int
    w_len: int   # padded primal length: d_run (1-D) / m·d1_loc (2-D)
    d_loc: int   # 2-D per-shard feature count (0 on 1-D)
    d1_loc: int  # 2-D per-shard padded slice length (0 on 1-D)
    ell: bool
    use_k: bool
    interpret: bool
    X: object
    sq_norms: object
    ridx: object  # pod rowmap gather indices (None off-pod)
    delay_rounds: int
    pod_delay_rounds: int
    gap_every: int
    record: bool
    tuning: object  # repro.dist.mesh.SelfTuning (resolved knobs)
    shrink_tol: float
    repack_threshold: float
    adaptive_ratio: float
    seed: int
    n_tasks: int = 0     # multi-task K (0 = binary, DESIGN.md §16)
    Y: object = None     # placed (K, n_pad) ±1 label matrix (None = binary)
    ragged: bool = False  # X is packed ragged rows (a CsrMatrix input)
    layout: object = None  # RowLayout of the packed ragged rows
    gap_engine: str = "xla"  # the 1-D gap's products (_gap_onehot)


def _place_labels(mesh, y, *, n, n_pad, ridx, pod_on):
    """Pad a host (K, n) ±1 label matrix to the solve's row layout —
    padding slots get +1.0 (inert: their rows are all-zero and masked
    out of every sum, the fold just has to stay finite) — and place it
    replicated over the row axes with the leading task axis on the
    ``task`` mesh axis when one exists.  Returns ``(K, Y_placed)``."""
    Y = jnp.asarray(y, jnp.float32)
    K = int(Y.shape[0])
    if pod_on:
        Yp = jnp.concatenate([Y, jnp.ones((K, 1), jnp.float32)],
                             axis=1)[:, ridx]
    else:
        Yp = jnp.ones((K, n_pad), jnp.float32).at[:, :n].set(Y)
    tax = "task" if "task" in mesh.axis_names else None
    return K, jax.device_put(Yp, named(mesh, tax, data_axes(mesh)))


def _refuse_ragged(mesh, *, y, shrink_every):
    """A ``CsrMatrix`` runs the pipelined 1-D solve only: the other
    paths take fixed-width rows, and padding heavy-tailed rows to the
    longest is what the ragged layout exists to avoid."""
    why = None
    if "model" in mesh.axis_names:
        why = "the 2-D feature split (a 'model' mesh axis)"
    elif "pod" in mesh.axis_names:
        why = "the pod solver (a 'pod' mesh axis)"
    elif y is not None or "task" in mesh.axis_names:
        why = "the task axis (a (K, n) label matrix)"
    elif shrink_every:
        why = "shrinking and its repack (shrink_every > 0)"
    if why is not None:
        raise ValueError(
            f"ragged rows (CsrMatrix) are not supported by {why}; they "
            f"run on the pipelined 1-D 'data' mesh only, and are never "
            f"padded to the longest row for another path")


def _pack_ragged_1d(mesh, X_host: CsrMatrix, *, n_loc: int, n_pad: int):
    """Pack ragged rows for a 1-D mesh (host, ``passcode.pack``) and place
    them: X = (cols, vals, gseg, ptr, wid), each sharded over ``data``;
    also the padded ‖x‖² (host) and the ``RowLayout`` counters."""
    p = mesh.shape["data"]
    with jax.profiler.TraceAnnotation("passcode.pack"):
        packed = pack_ragged(X_host, p, n_loc, grain=GRAIN)
        sq = _pad_host(X_host.row_sq_norms(), (n_pad,), 1.0, np.float32)
        tiles = -(-(packed.ptr % LANES + packed.wid) // LANES)
        layout = RowLayout(
            nnz=packed.nnz, slots_walked=packed.slots,
            buckets=int(np.unique(packed.wid[packed.wid > 0]).size),
            chunked_rows=int(np.sum(tiles > CHUNK_TILES)))
    data_sh = named(mesh, data_axes(mesh))
    X = tuple(jax.device_put(a, data_sh) for a in (
        packed.cols, packed.vals, packed.gseg, packed.ptr, packed.wid))
    return X, sq, layout


def _pad_host(a, shape, fill, dtype, rowmap=None):
    """``a`` copied into the leading corner of a ``fill``-valued host
    array of ``shape``, then, on a pod mesh, its rows gathered through
    ``rowmap`` (row n is the padding row).  Built in numpy so that
    ``device_put`` sends each shard straight to its device: the padded
    dataset never sits whole on one device."""
    a = np.asarray(a, dtype)
    out = np.full(shape, fill, dtype)
    out[tuple(slice(0, s) for s in a.shape)] = a
    return out if rowmap is None else out[rowmap]


@functools.partial(jax.profiler.annotate_function, name="passcode.prepare")
def prepare_solver(
    X_host,
    loss,
    *,
    mesh: Mesh | None = None,
    mesh_axes: tuple = ("data",),
    y=None,
    block_size: int = 64,
    delay_rounds: int = 0,
    pod_delay_rounds: int = 0,
    seed: int = 0,
    record: bool = True,
    use_kernel: bool | str = False,
    gap_every: int = 1,
    overlap: bool | str = "auto",
    shrink_every: int = 0,
    shrink_tol: float = 1e-3,
    repack: bool | str = "auto",
    repack_threshold: float = 0.5,
    adaptive: bool = False,
    adaptive_ratio: float = 0.95,
) -> SolverSetup:
    """Resolve the mesh and every admission policy, pad and place the
    dataset, and return the ``SolverSetup`` the solve entry points run
    against.  All knob validation (``pod_merge_policy``,
    ``pipeline_overlap``, ``resolve_self_tuning``) happens here, so the
    segmented resilience layer inherits it for free."""
    if mesh is None:
        if "task" in mesh_axes:
            n_dev = len(jax.devices())
            t_ax = 2 if n_dev % 2 == 0 else 1
            m_ax = (2 if "model" in mesh_axes
                    and (n_dev // t_ax) % 2 == 0 else 1)
            mesh = solver_mesh_tasks(task=t_ax, model=m_ax)
        elif "pod" in mesh_axes:
            n_dev = len(jax.devices())
            pods = 2 if n_dev % 2 == 0 else 1
            if "model" in mesh_axes:
                m_ax = 2 if (n_dev // pods) % 2 == 0 else 1
                mesh = solver_mesh_3d(pod=pods, model=m_ax)
            else:
                mesh = make_mesh((pods, n_dev // pods), ("pod", "data"))
        elif "model" in mesh_axes:
            mesh = solver_mesh_2d()
        else:
            mesh = solver_mesh("data")
    if "model" in mesh.axis_names and "data" not in mesh.axis_names:
        # legacy 1-D ("model",) mesh → (data=1, model=m): serial in i
        # within each round, features sharded
        mesh = make_mesh((1, mesh.devices.size), ("data", "model"),
                         devices=mesh.devices.reshape(-1))
    mesh = auto_mesh(mesh)
    pod_on = "pod" in mesh.axis_names
    if pod_on:
        pod_merge_policy(pod_delay_rounds, n_pods=mesh.shape["pod"],
                         record=record,
                         shrink_every=shrink_every, adaptive=adaptive,
                         overlap=overlap)
    elif pod_delay_rounds:
        raise ValueError(
            "pod_delay_rounds needs a mesh with a 'pod' axis")
    if y is not None:
        task_axis_policy(jnp.asarray(y).shape[0], mesh=mesh)
    elif "task" in mesh.axis_names:
        raise ValueError(
            "a 'task' mesh axis needs a (K, n) label matrix y "
            "(DESIGN.md §16)")
    ragged = isinstance(X_host, CsrMatrix)
    if ragged:
        _refuse_ragged(mesh, y=y, shrink_every=shrink_every)
    two_d = "model" in mesh.axis_names
    gap_every = max(int(gap_every), 1)
    p = mesh.shape["data"]
    pods = mesh.shape["pod"] if pod_on else 1
    data_sh = named(mesh, data_axes(mesh))
    row_sh = named(mesh, data_axes(mesh), None)

    if two_d:
        m = mesh.shape["model"]
        is_ell = isinstance(X_host, EllMatrix)
        ell = X_host if is_ell else dense_to_ell(X_host)
        n, d = ell.n_rows, ell.n_features
        fse = ell_column_split(ell, m)
        d_loc, k_loc = fse.d_loc, fse.k_loc
        # ceil twice on a pod mesh: each pod's contiguous row shard
        # carries its OWN padded tail (pod_row_layout), then subdivides
        # over "data"
        n_pod_loc = max(-(-n // pods), 1)
        n_loc = -(-n_pod_loc // p)  # ceil: the tail is padded
        n_pad = pods * p * n_loc
        use_k, interpret = _resolve_kernel_mode_feature(
            use_kernel, n_loc, k_loc, d_loc, block_size
        )
        overlap_on = pipeline_overlap(overlap, two_d=True, fused=use_k,
                                      delay_rounds=delay_rounds)
        if pod_on:
            # pod_merge_policy already rejected an explicit
            # overlap=True; "auto" resolves off — the in-flight (base,
            # Gram) psum is not valid under the merge-rescaled outer
            # schedule
            overlap_on = False
        tuning = resolve_self_tuning(shrink_every, repack, adaptive,
                                     overlap_knob=overlap,
                                     overlap_on=overlap_on,
                                     record=record)
        # lane-pad k_loc and the per-shard padded primal when fused;
        # pad rows to n_pad with all-padding rows (local id d_loc, 0)
        k_run = lane_pad(k_loc) if use_k else k_loc
        d1_loc = lane_pad(d_loc + 1) if use_k else d_loc + 1
        ridx = rows = None
        if pod_on:
            rowmap, _ = pod_row_layout(n, pods, per_pod_rows=p * n_loc)
            rows = rowmap.reshape(-1)  # global id, n = pad
            ridx = jnp.asarray(rows)
        lead = n + 1 if pod_on else n_pad
        cols = _pad_host(fse.indices, (lead, m, k_run), d_loc, np.int32,
                         rows)
        vals = _pad_host(fse.values, (lead, m, k_run), 0.0, np.float32,
                         rows)
        sq_norms = _pad_host(fse.row_sq_norms(), (lead,), 1.0, np.float32,
                             rows)
        x_sh = named(mesh, data_axes(mesh), "model", None)
        X = (jax.device_put(cols, x_sh), jax.device_put(vals, x_sh))
        n_tasks, Y = ((0, None) if y is None else _place_labels(
            mesh, y, n=n, n_pad=n_pad, ridx=ridx, pod_on=pod_on))
        return SolverSetup(
            mesh=mesh, loss=loss, two_d=True, pod_on=pod_on, pods=pods,
            p=p, m=m, n=n, d=d, n_loc=n_loc, n_pad=n_pad,
            n_blocks=_n_blocks(n_loc, block_size), block_size=block_size,
            w_len=m * d1_loc, d_loc=d_loc, d1_loc=d1_loc, ell=True,
            use_k=use_k, interpret=interpret, X=X,
            sq_norms=jax.device_put(sq_norms, data_sh), ridx=ridx,
            delay_rounds=delay_rounds, pod_delay_rounds=pod_delay_rounds,
            gap_every=gap_every, record=record, tuning=tuning,
            shrink_tol=shrink_tol, repack_threshold=repack_threshold,
            adaptive_ratio=adaptive_ratio, seed=seed,
            n_tasks=n_tasks, Y=Y)

    is_ell = isinstance(X_host, EllMatrix)
    if is_ell:
        n, d, k_max = X_host.n_rows, X_host.n_features, X_host.k_max
    elif ragged:
        n, d = X_host.n_rows, X_host.n_features
    else:
        n, d = X_host.shape
    # ceil twice on a pod mesh: each pod's contiguous row shard carries
    # its OWN padded tail (pod_row_layout), then subdivides over "data"
    n_pod_loc = max(-(-n // pods), 1)
    n_loc = -(-n_pod_loc // p)  # ceil: the tail is padded, not dropped
    n_pad = pods * p * n_loc
    ridx = rows = None
    if pod_on:
        rowmap, _ = pod_row_layout(n, pods, per_pod_rows=p * n_loc)
        rows = rowmap.reshape(-1)  # global id, n = padding
        ridx = jnp.asarray(rows)
    use_k, interpret = _resolve_kernel_mode(use_kernel, n_loc, d, ell=is_ell,
                                            block_size=block_size,
                                            ragged=ragged)
    # a 1-D mesh has no model-axis psum: "auto" resolves to no overlap,
    # an explicit True is an error
    pipeline_overlap(overlap, two_d=False, fused=use_k,
                     delay_rounds=delay_rounds)
    tuning = resolve_self_tuning(shrink_every, repack, adaptive,
                                 overlap_knob=overlap, overlap_on=False,
                                 record=record)
    layout = None
    if ragged:
        # the packed slots, padded only to the walk's granule; the primal
        # as on the ELL path (dummy slot at d, lane-padded when fused)
        d_run = lane_pad(d + 1) if use_k else d + 1
        X, sq_norms, layout = _pack_ragged_1d(mesh, X_host, n_loc=n_loc,
                                              n_pad=n_pad)
    elif is_ell:
        # the shard keeps its own width k_max on every engine (the fused
        # kernel lane-aligns a copy per dispatch, ``stream_rows``); pad
        # rows to n_pad with all-padding rows (index d, value 0)
        # padded primal with the dummy slot at index d (lane-padded for
        # clean tiling when fused); padding scatter-adds land there
        d_run = lane_pad(d + 1) if use_k else d + 1
        # pod layout: gather through the flattened rowmap with a padding
        # row appended at global index n — each pod's contiguous shard
        # lands with its own padded tail
        lead = n + 1 if pod_on else n_pad
        cols = _pad_host(X_host.indices, (lead, k_max), d, np.int32, rows)
        vals = _pad_host(X_host.values, (lead, k_max), 0.0, np.float32,
                         rows)
        sq_norms = _pad_host(X_host.row_sq_norms(), (lead,), 1.0,
                             np.float32, rows)
        X = (
            jax.device_put(cols, row_sh),
            jax.device_put(vals, row_sh),
        )
    else:
        X = jnp.asarray(X_host)
        # the kernel wants clean (8, 128) f32 tiling: lane-pad d with
        # zero columns (inert in every dot product; sliced off the
        # returned w); row padding is all-zero rows with q set to 1 so
        # their (never-selected) update stays finite
        d_run = lane_pad(d) if use_k else d
        if pod_on:
            X = jnp.zeros((n + 1, d_run), X.dtype).at[:n, :d].set(X)
            sq_norms = jnp.sum(X * X, axis=1).at[n].set(1.0)[ridx]
            X = X[ridx]
        else:
            if d_run != d or n_pad != n:
                X = jnp.zeros((n_pad, d_run), X.dtype).at[:n, :d].set(X)
            sq_norms = jnp.sum(X * X, axis=1)
            if n_pad != n:
                sq_norms = sq_norms.at[n:].set(1.0)
        X = jax.device_put(X, row_sh)
    n_tasks, Y = ((0, None) if y is None else _place_labels(
        mesh, y, n=n, n_pad=n_pad, ridx=ridx, pod_on=pod_on))
    return SolverSetup(
        mesh=mesh, loss=loss, two_d=False, pod_on=pod_on, pods=pods,
        p=p, m=1, n=n, d=d, n_loc=n_loc, n_pad=n_pad,
        n_blocks=_n_blocks(n_loc, block_size), block_size=block_size,
        w_len=d_run, d_loc=0, d1_loc=0, ell=is_ell, use_k=use_k,
        interpret=interpret, X=X,
        sq_norms=jax.device_put(sq_norms, data_sh), ridx=ridx,
        delay_rounds=delay_rounds, pod_delay_rounds=pod_delay_rounds,
        gap_every=gap_every, record=record, tuning=tuning,
        shrink_tol=shrink_tol, repack_threshold=repack_threshold,
        adaptive_ratio=adaptive_ratio, seed=seed,
        n_tasks=n_tasks, Y=Y, ragged=ragged, layout=layout,
        gap_engine=("pallas-onehot-" + ("interpret" if interpret
                                         else "compiled")
                    if _gap_onehot(use_k, is_ell or ragged, d_run)
                    else "xla"))


def _init_alpha_w(setup: SolverSetup, alpha0=None, w0=None):
    """Global padded (α, w) for a solve — zeros, or the PR-7 warm-start
    re-blocking of carried state onto whatever layout ``setup`` has
    (the elastic pod join/leave path, reused verbatim by checkpoint
    restore across changed meshes).  A carried ``alpha0``/``w0``
    *shorter* than the setup's n/d is the streaming-append warm start
    (DESIGN.md §15): old coordinates keep their duals, freshly appended
    rows enter at α = 0 (their optimal start — they have made no
    contribution to w yet).

    On a multi-task setup the carried state is a (K, n')/(K, d') stack
    and the same re-blocking runs vmapped over the task rows, so the
    elastic/warm-start semantics are per-class identical to K
    independent binary restores."""
    if setup.n_tasks:
        K = setup.n_tasks
        if alpha0 is None and w0 is None:
            return (jnp.zeros((K, setup.n_pad), jnp.float32),
                    jnp.zeros((K, setup.w_len), jnp.float32))
        a2 = (None if alpha0 is None
              else jnp.asarray(alpha0, jnp.float32).reshape(K, -1))
        w2 = (None if w0 is None
              else jnp.asarray(w0, jnp.float32).reshape(K, -1))
        if a2 is None:
            return jax.vmap(
                lambda wv: _init_alpha_w_single(setup, None, wv))(w2)
        if w2 is None:
            return jax.vmap(
                lambda av: _init_alpha_w_single(setup, av, None))(a2)
        return jax.vmap(
            lambda av, wv: _init_alpha_w_single(setup, av, wv))(a2, w2)
    return _init_alpha_w_single(setup, alpha0, w0)


def _init_alpha_w_single(setup: SolverSetup, alpha0=None, w0=None):
    n, n_pad, d = setup.n, setup.n_pad, setup.d
    if alpha0 is None:
        alpha = jnp.zeros((n_pad,), jnp.float32)
    else:
        a0 = jnp.asarray(alpha0, jnp.float32).reshape(-1)[:n]
        a_full = jnp.zeros((n + 1,), jnp.float32).at[: a0.shape[0]].set(a0)
        alpha = (a_full[setup.ridx] if setup.pod_on else jnp.concatenate(
            [a_full[:n], jnp.zeros((n_pad - n,), jnp.float32)]))
    if setup.two_d:
        m, d_loc, d1_loc = setup.m, setup.d_loc, setup.d1_loc
        # per-shard padded primal slices, concatenated: shard j owns
        # w[j·d₁_loc : (j+1)·d₁_loc), dummy slot at local index d_loc
        w = jnp.zeros((m * d1_loc,), jnp.float32)
        if w0 is not None:
            v0 = jnp.asarray(w0, jnp.float32).reshape(-1)[:d]
            wp = jnp.zeros((m * d_loc,), jnp.float32).at[
                : v0.shape[0]].set(v0).reshape(m, d_loc)
            w = jnp.zeros((m, d1_loc), jnp.float32).at[:, :d_loc].set(
                wp).reshape(-1)
    else:
        w = jnp.zeros((setup.w_len,), jnp.float32)
        if w0 is not None:
            v0 = jnp.asarray(w0, jnp.float32).reshape(-1)[:d]
            w = w.at[: v0.shape[0]].set(v0)
    return alpha, w


def build_pipeline(setup: SolverSetup, *, epochs: int,
                   total_epochs: int | None = None,
                   segmented: bool = False, watchdog: bool = False,
                   watchdog_blowup: float = 4.0,
                   watchdog_floor: float = 1e-3, fault=None,
                   delay_rounds: int | None = None,
                   pod_delay_rounds: int | None = None,
                   overlap_on: bool | None = None):
    """Build the pipelined solve for a prepared setup — the one place
    the two builders are dispatched from.  The override knobs
    (``delay_rounds``/``pod_delay_rounds``/``overlap_on``) exist for
    the degradation ladder (DESIGN.md §14): a degraded retry rebuilds
    the pipeline synchronous against the same ``SolverSetup``."""
    dr = setup.delay_rounds if delay_rounds is None else int(delay_rounds)
    pdr = (setup.pod_delay_rounds if pod_delay_rounds is None
           else int(pod_delay_rounds))
    st = setup.tuning
    common = dict(
        epochs=epochs, block_size=setup.block_size,
        n_blocks=setup.n_blocks, n_rows=setup.n, delay_rounds=dr,
        use_kernel=setup.use_k, interpret=setup.interpret,
        record=setup.record, gap_every=setup.gap_every,
        shrink_every=st.shrink_every, shrink_tol=setup.shrink_tol,
        repack_threshold=(setup.repack_threshold if st.repack else None),
        adaptive=st.adaptive, adaptive_ratio=setup.adaptive_ratio,
        pod_delay_rounds=pdr, total_epochs=total_epochs,
        segmented=segmented, watchdog=watchdog,
        watchdog_blowup=watchdog_blowup, watchdog_floor=watchdog_floor,
        fault=fault)
    if setup.two_d:
        ov = st.overlap if overlap_on is None else bool(overlap_on)
        if dr < 1:
            ov = False  # the in-flight psum needs the delayed round
        return make_sharded_pipeline_2d(setup.mesh, setup.loss,
                                        overlap=ov, **common)
    return make_sharded_pipeline(setup.mesh, setup.loss, ell=setup.ell,
                                 ragged=setup.ragged, **common)


@functools.partial(jax.profiler.annotate_function,
                   name="passcode.init_state")
def init_pipeline_state(setup: SolverSetup, *, total_epochs: int,
                        watchdog: bool = False, alpha0=None, w0=None,
                        delay_rounds: int | None = None,
                        pod_delay_rounds: int | None = None,
                        overlap_on: bool | None = None):
    """Fresh epoch-0 ``SolverState`` (global arrays, mesh-placed) for
    the segmented solver — exactly the state a ``segmented=True``
    pipeline consumes and returns.  ``alpha0``/``w0`` warm-start it
    (the elastic-restore path re-blocks them onto this setup's
    layout)."""
    dr = setup.delay_rounds if delay_rounds is None else int(delay_rounds)
    pdr = (setup.pod_delay_rounds if pod_delay_rounds is None
           else int(pod_delay_rounds))
    st = setup.tuning
    ov = st.overlap if overlap_on is None else bool(overlap_on)
    if dr < 1:
        ov = False
    shrink_on = st.shrink_every > 0
    dyn = (shrink_on or st.adaptive) and not ov and not setup.pod_on
    n_gaps = (_gap_slots(int(total_epochs), setup.gap_every)
              if setup.record else 0)
    alpha, w = _init_alpha_w(setup, alpha0, w0)
    delay0 = int(pdr > 0) if setup.pod_on else dr
    pod_fifo = pdr if (setup.pod_on and pdr > 0) else 0
    key = jax.random.PRNGKey(setup.seed)
    if setup.n_tasks:
        # every task starts on the SAME chain — matching K independent
        # binary solves at this seed, which is what the loop-over-K
        # reference (and the K=1 bit-identity contract) compares against
        key = jnp.broadcast_to(key, (setup.n_tasks,) + key.shape)
    state = _fresh_carry(alpha, w, jnp.zeros_like(w), key, n_gaps,
                         n_blocks=setup.n_blocks, dyn=dyn,
                         shrink_on=shrink_on, adaptive=st.adaptive,
                         delay0=delay0, pod_fifo=pod_fifo,
                         watchdog=watchdog, n_tasks=setup.n_tasks)
    if shrink_on:
        # global view of the device-local ``valid`` masks (the padding
        # rows of every pod tail excluded)
        act = (setup.ridx < setup.n if setup.pod_on
               else jnp.arange(setup.n_pad) < setup.n)
        state["act"] = (jnp.broadcast_to(act, (setup.n_tasks,)
                                         + act.shape)
                        if setup.n_tasks else act)
    return device_put_state(setup, state)


def device_put_state(setup: SolverSetup, state: dict) -> dict:
    """Place a global ``SolverState`` onto the mesh with the segmented
    builders' specs: dual-sized leaves over the row axes, primal-sized
    leaves over ``model`` (2-D) or replicated (1-D), the pod FIFO
    sharded on its trailing primal axis, everything else replicated.
    This is the elastic-resharding point: restored host arrays re-shard
    here onto whatever mesh ``setup`` carries."""
    mesh = setup.mesh
    rep_sh = replicated(mesh)
    if setup.n_tasks:
        # multi-task layout: every leaf except the shared epoch scalar
        # carries the leading (K,) axis — placed on the 'task' mesh
        # axis when one exists, unsharded otherwise
        tax = "task" if "task" in mesh.axis_names else None
        data_sh = named(mesh, tax, data_axes(mesh))
        w_sh = (named(mesh, tax, "model") if setup.two_d
                else named(mesh, tax))
        pbuf_sh = (named(mesh, tax, None, "model") if setup.two_d
                   else named(mesh, tax))
        task_sh = named(mesh, tax)
    else:
        data_sh = named(mesh, data_axes(mesh))
        w_sh = named(mesh, "model") if setup.two_d else replicated(mesh)
        pbuf_sh = (named(mesh, None, "model") if setup.two_d
                   else replicated(mesh))
        task_sh = rep_sh

    def place(k, v):
        if k == "epoch":
            return jax.device_put(v, rep_sh)
        if k in ("alpha", "act"):
            return jax.device_put(v, data_sh)
        if k in ("w", "dw", "dwo"):
            return jax.device_put(v, w_sh)
        if k == "pbuf":
            return jax.device_put(v, pbuf_sh)
        return jax.device_put(v, task_sh)

    return {k: place(k, v) for k, v in state.items()}


@functools.partial(jax.profiler.annotate_function, name="passcode.finalize")
def finalize_state(setup: SolverSetup, state: dict,
                   *, epochs: int) -> ShardedResult:
    """``ShardedResult`` out of a segmented run's final ``SolverState``:
    flush the in-flight aggregate (exact zeros when the run ended
    synchronous — the add is then inert), drain the pod FIFO, and
    un-pad exactly like the whole-solve entry point."""
    dw = state["pbuf"].sum(-2) if "pbuf" in state else state["dw"]
    w = state["w"] + dw
    return _finalize(setup, state["alpha"], w, state["gaps"], epochs,
                     state["epsb"], state["actb"], state["delayb"])


def engine_name(setup: SolverSetup) -> str:
    """``<layout>/<engine>`` of a prepared solve: layout ``dense``,
    ``ell``, ``ragged`` (packed ragged rows) or ``feature`` (2-D), engine
    ``jnp`` or ``pallas`` with its mode — ``compiled`` on TPU,
    ``interpret`` elsewhere; the 1-D ELL and ragged kernels, which
    stream their rows from HBM, are ``pallas-stream``."""
    layout = ("feature" if setup.two_d else "ragged" if setup.ragged
              else "ell" if setup.ell else "dense")
    if not setup.use_k:
        return f"{layout}/jnp"
    kernel = "pallas-stream" if layout in ("ell", "ragged") else "pallas"
    mode = "interpret" if setup.interpret else "compiled"
    return f"{layout}/{kernel}-{mode}"


def _finalize(setup: SolverSetup, alpha, w, gaps_arr, epochs,
              eps_arr, act_arr, delay_arr):
    """Un-pad a finished solve back to user coordinates: invert the pod
    rowmap gather (padding slots all land on the sliced-off index n),
    stitch the true primal out of the 2-D per-shard padded slices, and
    slice off row/lane padding.  On a multi-task setup every step runs
    over the trailing axes of the (K, …) stacks, so the result carries
    (K, n) duals / (K, d) weights / (K, n_gaps) records."""
    n, d = setup.n, setup.d
    eng, gap_eng = engine_name(setup), setup.gap_engine
    if setup.n_tasks:
        if setup.pod_on:
            alpha = jax.vmap(
                lambda a: jnp.zeros((n + 1,), jnp.float32)
                .at[setup.ridx].set(a))(alpha)
        if setup.two_d:
            w = w.reshape(setup.n_tasks, setup.m,
                          setup.d1_loc)[:, :, :setup.d_loc]
            w = w.reshape(setup.n_tasks, -1)[:, :d]
        else:
            w = w[:, :d]
        return ShardedResult(alpha[:, :n], w, gaps_arr, epochs, eps_arr,
                             act_arr, delay_arr, eng, gap_eng)
    if setup.pod_on:
        alpha = jnp.zeros((n + 1,), jnp.float32).at[setup.ridx].set(alpha)
    if setup.two_d:
        w = w.reshape(setup.m, setup.d1_loc)[:, :setup.d_loc]
        w = w.reshape(-1)[:d]
    else:
        w = w[:d]
    return ShardedResult(alpha[:n], w, gaps_arr, epochs, eps_arr,
                         act_arr, delay_arr, eng, gap_eng)


def _validate_solver_inputs(X_host, y, loss):
    """Fail fast at the solver mouth (DESIGN.md §14): a non-finite
    feature value, a non-positive C, or a label outside {−1, +1} each
    used to surface only as a silently diverged solve epochs later.
    Returns ``X_host`` with the labels folded in (x_i = y_i·ẋ_i — the
    convention every solver path already assumes) when ``y`` is
    given."""
    C = getattr(loss, "C", None)
    if C is not None and not float(C) > 0:
        raise ValueError(f"loss.C must be positive, got {C!r}")
    vals = (X_host.values if isinstance(X_host, (EllMatrix, CsrMatrix))
            else X_host)
    if not np.all(np.isfinite(np.asarray(vals))):
        raise ValueError("X contains non-finite entries (NaN/Inf)")
    if y is None:
        return X_host
    y = np.asarray(jax.device_get(y), np.float32).reshape(-1)
    n = (X_host.n_rows if isinstance(X_host, (EllMatrix, CsrMatrix))
         else X_host.shape[0])
    if y.shape[0] != n:
        raise ValueError(f"y has {y.shape[0]} labels for {n} rows")
    if not np.all(np.isfinite(y)):
        raise ValueError("y contains non-finite entries (NaN/Inf)")
    if not np.all(np.isin(y, (-1.0, 1.0))):
        raise ValueError(
            "labels must be in {-1, +1}; the solver folds them into X "
            "as x_i = y_i*x_i")
    if isinstance(X_host, EllMatrix):
        return EllMatrix(X_host.indices,
                         np.asarray(X_host.values) * y[:, None],
                         X_host.n_features)
    if isinstance(X_host, CsrMatrix):
        return X_host._replace(values=np.asarray(X_host.values, np.float32)
                               * y[X_host.row_of_entry()])
    return np.asarray(X_host) * y[:, None]


def _validate_multitask_labels(X_host, Y):
    """The multi-task mouth (DESIGN.md §16): a (K, n) ±1 one-vs-rest
    label matrix — validated, NOT folded into X.  Shared-X tasks cannot
    pre-fold (each class flips a different row subset), so the engines
    fold on read instead; the returned float32 matrix is what
    ``prepare_solver`` pads and places."""
    Y = np.asarray(jax.device_get(Y), np.float32)
    if Y.ndim != 2 or Y.shape[0] < 1:
        raise ValueError(
            f"multi-task labels must be a (K, n) matrix, got shape "
            f"{Y.shape}")
    n = (X_host.n_rows if isinstance(X_host, (EllMatrix, CsrMatrix))
         else X_host.shape[0])
    if Y.shape[1] != n:
        raise ValueError(
            f"label matrix has {Y.shape[1]} columns for {n} rows")
    if not np.all(np.isfinite(Y)):
        raise ValueError("Y contains non-finite entries (NaN/Inf)")
    if not np.all(np.isin(Y, (-1.0, 1.0))):
        raise ValueError(
            "multi-task labels must be in {-1, +1} (see "
            "repro.data.ovr_labels)")
    return Y


def sharded_passcode_solve(
    X_host,
    loss,
    *,
    mesh: Mesh | None = None,
    mesh_axes: tuple = ("data",),
    epochs: int = 10,
    block_size: int = 64,
    delay_rounds: int = 0,
    pod_delay_rounds: int = 0,
    seed: int = 0,
    record: bool = True,
    alpha0=None,
    w0=None,
    y=None,
    use_kernel: bool | str = False,
    gap_every: int = 1,
    overlap: bool | str = "auto",
    shrink_every: int = 0,
    shrink_tol: float = 1e-3,
    repack: bool | str = "auto",
    repack_threshold: float = 0.5,
    adaptive: bool = False,
    adaptive_ratio: float = 0.95,
) -> ShardedResult:
    """Distributed PASSCoDe-Atomic.  ``X_host``: dense (n, d) array, an
    ``EllMatrix`` (the sparse fast path — per-update work drops from
    O(d) to O(k_max)) or a ``CsrMatrix`` (rows of unequal length, packed
    to each row's own length: per-update work O(nnz_i); 1-D ``data``
    mesh only, DESIGN.md §9); rows are sharded across the mesh's
    ``data`` axis, padded to p-divisibility with masked zero rows (never
    dropped).

    ``mesh_axes=("data", "model")`` (or passing a mesh that carries a
    ``model`` axis) selects the 2-D feature-sharded engine for
    webspam/kddb-scale d (DESIGN.md §10): w and the feature dimension
    shard along ``model`` as per-feature-shard local ELL slices, partial
    dot products psum over ``model``, and no replicated primal exists
    anywhere.  Dense ``X_host`` converts to ELL first on that path.

    ``use_kernel``: False (pure-jnp block update), True (fused Pallas
    block engine — interpret mode off-TPU), or "auto" (fused only on TPU
    when the shard fits VMEM — the dense, ELL, or feature-sharded policy
    as appropriate; see ``_resolve_kernel_mode``).

    ``gap_every``: with ``record=True``, compute the duality gap every
    that many epochs (plus the final one).  Gap values stay on device
    until the solve finishes, so recording no longer host-syncs (and
    thereby serializes) every epoch.

    The whole multi-epoch solve is one jitted dispatch — block
    permutations drawn on-device inside the shard_map body, gaps
    accumulated into an on-device buffer (DESIGN.md §11).

    ``overlap``: on the 2-D fused path with ``delay_rounds ≥ 1``,
    double-buffer the block round so the ``model``-axis (base, Gram)
    psum of block t overlaps the gram kernel of block t+1
    (``_scan_rounds_overlap``).  "auto" (default) enables it exactly
    there; True elsewhere raises (``repro.dist.mesh.pipeline_overlap``).

    Self-tuning knobs (DESIGN.md §12; validated by
    ``repro.dist.mesh.resolve_self_tuning``):

    ``shrink_every ≥ 1`` turns on on-device active-set shrinking: every
    that many epochs each device recomputes the LIBLINEAR projected-
    gradient mask from its carried (α, effective w) and frozen
    coordinates take exact zero-delta updates; the final epoch always
    runs unshrunk (the recovery pass), so results match the unshrunk
    solve on converged problems.  ``shrink_tol`` is the projected-
    gradient threshold.  ``repack`` ∈ {"auto", True, False}: once the
    global active fraction drops below ``repack_threshold``, redraw each
    epoch's blocks over the compacted active set and skip the now-empty
    tail rounds — epochs get *shorter*, the wall-clock win on
    mostly-converged rcv1/news20-style profiles.  ``adaptive`` runs the
    gap-trend controller (``adaptive_delay_policy``): each recorded gap
    decides whether following epochs keep the delayed (async) round
    schedule or drop to synchronous — a one-way latch seeded by
    ``delay_rounds`` (seed 1 to start async); ``adaptive_ratio`` is its
    improvement threshold (0.95 backs off only on a hard stall, 0.5
    anneals async→sync once the gap stops halving per record).  The
    result carries the live per-record metrics: ``eps``
    (the backward-error ‖w(α) − ŵ‖ of ``core/backward_error.py``),
    ``active`` (global active fraction) and ``delay`` (effective flag),
    all aligned with ``gaps``.

    A mesh with a ``pod`` outer axis (``mesh_axes=("pod", "data")`` or
    ``("pod", "data", "model")``; build with ``repro.dist.mesh.
    solver_mesh_3d``) runs the double-async Hybrid-DCA scheme
    (DESIGN.md §13): each pod solves PASSCoDe on its own contiguous row
    shard (``repro.data.sparse.pod_row_layout`` — duals never leave the
    pod), and per epoch the pods' primal deltas merge as a CoCoA β_K=1
    average through a ``pod_delay_rounds``-deep FIFO — the bounded-
    staleness model of a slow cross-pod allreduce.  ``pod_delay_rounds
    = 0`` is a synchronous CoCoA outer round (the ``repro.core.cocoa``
    oracle); admission is validated by ``repro.dist.mesh.
    pod_merge_policy`` (no shrinking/overlap;
    ``adaptive`` becomes the pod-level FIFO-drain latch).  ``alpha0`` /
    ``w0`` warm-start the solve from carried state — re-blocked onto
    whatever pod count the mesh has, which is how elastic pod
    join/leave works (``tests/test_elastic.py``).

    ``y`` (optional): ±1 labels to validate and fold into X as
    x_i = y_i·ẋ_i — the convention every solver path assumes when X
    arrives pre-folded.  With or without ``y`` the mouth validates its
    inputs (finite X, positive C) before anything touches the mesh
    (DESIGN.md §14); the segmented fault-tolerant variant of this
    entry point lives in ``repro.resilience.solve_segmented``.
    """
    y_host = None if y is None else np.asarray(jax.device_get(y))
    if y_host is not None and y_host.ndim == 2:
        # multi-task mouth: (K, n) one-vs-rest label matrix — validated
        # but NOT folded (shared X), threaded to the engines instead
        Y_host = _validate_multitask_labels(X_host, y_host)
        X_host = _validate_solver_inputs(X_host, None, loss)
    else:
        Y_host = None
        X_host = _validate_solver_inputs(X_host, y, loss)
    setup = prepare_solver(
        X_host, loss, mesh=mesh, mesh_axes=mesh_axes, y=Y_host,
        block_size=block_size, delay_rounds=delay_rounds,
        pod_delay_rounds=pod_delay_rounds, seed=seed, record=record,
        use_kernel=use_kernel, gap_every=gap_every, overlap=overlap,
        shrink_every=shrink_every, shrink_tol=shrink_tol, repack=repack,
        repack_threshold=repack_threshold, adaptive=adaptive,
        adaptive_ratio=adaptive_ratio)
    st = setup.tuning
    tax = "task" if "task" in setup.mesh.axis_names else None
    if setup.n_tasks:
        data_sh = named(setup.mesh, tax, data_axes(setup.mesh))
        w_sh = (named(setup.mesh, tax, "model") if setup.two_d
                else named(setup.mesh, tax))
    else:
        data_sh = named(setup.mesh, data_axes(setup.mesh))
        w_sh = (named(setup.mesh, "model") if setup.two_d
                else replicated(setup.mesh))
    alpha, w = _init_alpha_w(setup, alpha0, w0)
    alpha = jax.device_put(alpha, data_sh)
    w = jax.device_put(w, w_sh)
    carry_dw = jax.device_put(jnp.zeros_like(w), w_sh)
    key = jax.random.PRNGKey(setup.seed)
    if setup.n_tasks:
        # identical per-task chains: each class replays the binary
        # solve's draws exactly, matching the loop-over-K reference
        key = jnp.broadcast_to(key, (setup.n_tasks,) + key.shape)

    solve_fn = build_pipeline(setup, epochs=epochs)
    # identical block draws on the 1-D and 2-D paths at equal p and
    # seed, so the two engines run the same update sequence
    alpha, w, carry_dw, gaps_arr, eps_arr, act_arr, delay_arr = (
        solve_fn(setup.X, setup.sq_norms, alpha, w, key, carry_dw,
                 setup.Y))
    if (setup.delay_rounds > 0 or st.shrink_every or st.adaptive
            or setup.pod_delay_rounds > 0):
        w = w + carry_dw  # flush in-flight aggregate (0 when sync)
    return _finalize(setup, alpha, w, gaps_arr, epochs, eps_arr,
                     act_arr, delay_arr)
