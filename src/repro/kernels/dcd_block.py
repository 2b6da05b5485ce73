"""Pallas TPU kernel: block-resident dual coordinate descent epoch.

TPU adaptation of the PASSCoDe hot loop (DESIGN.md §2, §6).  The
GPU/multicore original races on a shared DRAM ``w``; the TPU version
makes the working set explicit:

  * rows arrive in VMEM as dense (BLOCK_ROWS, d) tiles (one grid step per
    tile — ELL/CSR rows are densified into tiles by the op wrapper);
  * ``w`` lives in VMEM for the *whole epoch*: its BlockSpec index_map is
    constant, and on TPU the grid executes sequentially, so each grid
    step sees the previous step's writes — serial-DCD-exact semantics
    with zero locking;
  * within a tile, updates run sequentially (fori_loop): w·x_t is a VPU
    reduction over d lanes, the closed-form (or Newton, for logistic) δ
    is scalar work, and the rank-1 update w += δ·x_t is a vector axpy.

Two addressing modes share the δ machinery:

  contiguous (``idx=None``) — grid step i processes rows
    [i·B, (i+1)·B) in order; only the current tile is VMEM-resident.
  indexed (``idx=``) — grid step i processes the arbitrary *local* row
    ids idx[i·B:(i+1)·B], gathered from a fully VMEM-resident X; α is
    carried across steps like w.  This computes exactly what the sharded
    solver's ``_local_block_update`` computes on a permuted block, which
    is how ``repro.core.sharded`` fuses its per-device round
    (``use_kernel=True`` on a dense shard).  The VMEM feasibility
    policy for the resident shard lives in ``repro.dist.mesh``
    (``dcd_kernel_fits`` / ``dcd_block_rows``).  Indexed mode also takes
    an optional ``y`` (±1 per row) folded *on read* — wx ← y_i·(w·x_i),
    scatter ← (δ·y_i)·x_i — so K one-vs-rest tasks can share one
    unfolded X (DESIGN.md §16); ``y=None`` feeds an all-ones operand,
    which is bit-identical to the pre-folded path (±1 multiplies only
    flip the sign bit).

The one-variable subproblem is solved by the *same* ``loss.delta`` the
jnp solvers use (``repro.core.duals``: hinge and squared-hinge closed
forms, logistic via safeguarded Newton) — the loss object is a frozen
dataclass, hashable, and traces fine inside the kernel, so the fused and
unfused paths share one definition of the update math.

dtype: f32 accumulators (α, w); X tiles may be f32 or bf16 (cast on use).

VMEM budget per grid step (f32): BLOCK_ROWS·d (tile) + 2·d (w, x) +
3·BLOCK_ROWS (α, q, scratch) ≈ 256·8192·4B ≈ 8 MiB at the default block —
inside the ~16 MiB/core budget, and d is lane-aligned to 128 by the
wrapper for clean (8,128) f32 tiling.  The indexed mode instead holds the
whole (n_loc, d) shard: see ``repro.dist.mesh.dcd_kernel_vmem_bytes``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _legacy_loss(c: float, sq_hinge: bool):
    """Loss object for the pre-``loss=`` API (``c``/``sq_hinge`` flags).

    Imported lazily: ``repro.core`` imports ``repro.kernels`` (the solver
    wires the kernel in), so a module-level import here would be a cycle.
    """
    from repro.core.duals import Hinge, SquaredHinge

    return (SquaredHinge if sq_hinge else Hinge)(C=c)


def _dcd_tile_kernel(
    x_ref,  # (B, d)  row tile, VMEM
    alpha_ref,  # (B, 1)  dual block, VMEM
    q_ref,  # (B, 1)  row squared norms
    w_ref,  # (1, d)  primal — full vector, constant index_map (carried)
    alpha_out,  # (B, 1)
    w_out,  # (1, d)
    *,
    loss,
    block_rows: int,
):
    # First grid step must seed the carried w output; afterwards w_out
    # already holds the running value (same buffer every step).
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        w_out[...] = w_ref[...]

    def body(t, w):
        x = x_ref[pl.ds(t, 1), :].astype(jnp.float32)  # (1, d)
        wx = jnp.sum(w * x)
        a = alpha_ref[pl.ds(t, 1), :]  # (1, 1)
        q = q_ref[pl.ds(t, 1), :]
        delta = loss.delta(a, wx, q)
        alpha_out[pl.ds(t, 1), :] = a + delta
        return w + delta * x  # rank-1 axpy, stays in registers/VMEM

    w = jax.lax.fori_loop(0, block_rows, body, w_out[...].astype(jnp.float32))
    w_out[...] = w


def _dcd_indexed_kernel(
    idx_ref,  # (B, 1)  int32 local row ids for this grid step
    x_ref,  # (n, d)  whole shard, VMEM-resident (constant index_map)
    alpha_ref,  # (n, 1)  duals — full vector (seeds the carried output)
    q_ref,  # (n, 1)  row squared norms
    act_ref,  # (n, 1)  active-set mask (f32 0/1; all-ones = no shrinking)
    y_ref,  # (n, 1)  row labels (±1; all-ones = pre-folded rows)
    w_ref,  # (1, d)  primal (seeds the carried output)
    alpha_out,  # (n, 1)  carried across grid steps
    w_out,  # (1, d)  carried across grid steps
    *,
    loss,
    block_rows: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        alpha_out[...] = alpha_ref[...]
        w_out[...] = w_ref[...]

    def body(t, w):
        i = idx_ref[t, 0]
        x = x_ref[pl.ds(i, 1), :].astype(jnp.float32)  # gather one row
        yi = y_ref[pl.ds(i, 1), :]  # (1, 1) ±1 — folds the row on read
        wx = yi[0, 0] * jnp.sum(w * x)
        a = alpha_out[pl.ds(i, 1), :]  # read the running α, not the seed
        q = q_ref[pl.ds(i, 1), :]
        # frozen (shrunk) coordinates take the exact zero-delta update
        delta = jnp.where(
            act_ref[pl.ds(i, 1), :] > 0.0, loss.delta(a, wx, q), 0.0
        )
        alpha_out[pl.ds(i, 1), :] = a + delta  # scatter back
        return w + (delta * yi) * x

    w = jax.lax.fori_loop(0, block_rows, body, w_out[...].astype(jnp.float32))
    w_out[...] = w


def dcd_epoch_pallas_call(
    X,  # (n, d) dense, d % 128 == 0; n % block_rows == 0 if idx is None
    alpha,  # (n,)
    w,  # (d,)
    sq_norms,  # (n,)
    *,
    c: float = 1.0,
    sq_hinge: bool = False,
    loss=None,  # overrides c/sq_hinge: any repro.core.duals-style loss
    idx=None,  # (m,) int32 row ids, m % block_rows == 0 → indexed mode
    block_rows: int = 256,
    interpret: bool = False,
    active=None,  # (n,) 0/1 active-set mask (indexed mode only)
    y=None,  # (n,) ±1 labels folded on read (indexed mode only)
):
    n, d = X.shape
    if loss is None:
        loss = _legacy_loss(c, sq_hinge)
    assert active is None or idx is not None, (
        "active-set masking needs the indexed mode")
    assert y is None or idx is not None, (
        "in-kernel label folding needs the indexed mode")
    alpha2 = alpha.reshape(n, 1).astype(jnp.float32)
    q2 = sq_norms.reshape(n, 1).astype(jnp.float32)
    w2 = w.reshape(1, d).astype(jnp.float32)

    if idx is None:
        assert n % block_rows == 0, (n, block_rows)
        grid = (n // block_rows,)
        kernel = functools.partial(
            _dcd_tile_kernel, loss=loss, block_rows=block_rows
        )
        alpha_out, w_out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),  # row tile
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),  # alpha
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),  # sq norms
                pl.BlockSpec((1, d), lambda i: (0, 0)),  # w: constant map
            ],
            out_specs=[
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),  # carried
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, d), jnp.float32),
            ],
            interpret=interpret,
        )(X, alpha2, q2, w2)
        return alpha_out.reshape(n), w_out.reshape(d)

    m = idx.shape[0]
    assert m % block_rows == 0, (m, block_rows)
    grid = (m // block_rows,)
    idx2 = idx.reshape(m, 1).astype(jnp.int32)
    if active is None:
        act2 = jnp.ones((n, 1), jnp.float32)
    else:
        act2 = active.reshape(n, 1).astype(jnp.float32)
    if y is None:
        y2 = jnp.ones((n, 1), jnp.float32)
    else:
        y2 = y.reshape(n, 1).astype(jnp.float32)
    kernel = functools.partial(
        _dcd_indexed_kernel, loss=loss, block_rows=block_rows
    )
    alpha_out, w_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),  # idx block
            pl.BlockSpec((n, d), lambda i: (0, 0)),  # X: whole shard
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # alpha seed
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # sq norms
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # active mask
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # row labels
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # w seed
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # carried α
            pl.BlockSpec((1, d), lambda i: (0, 0)),  # carried w
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(idx2, X, alpha2, q2, act2, y2, w2)
    return alpha_out.reshape(n), w_out.reshape(d)
