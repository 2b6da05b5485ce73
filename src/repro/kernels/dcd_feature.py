"""Pallas TPU kernels: feature-sharded (2D data × model) block DCD.

The 2D solver (DESIGN.md §10) shards w and the feature dimension along
``model``: each device holds one ``FeatureShardedEll`` slice — (n_loc,
k̃_loc) *local* column ids / values into its own d₁_loc-word primal
shard — so no replicated primal exists anywhere.  The exact per-update
rule needs the FULL wᵀx_i, i.e. a psum over ``model`` per update, and a
collective cannot run inside a ``pallas_call``.  The fused path therefore
restructures a block of B sequential updates around the identity

    wᵀx_t at step t  =  (w₀ + Σ_{s<t} δ_s x_s)ᵀ x_t
                     =  base_t + Σ_{s<t} δ_s · G[s, t]

with base_t = w₀ᵀx_t and G the block's B×B Gram matrix — both additive
over feature shards.  That turns B per-update psums of scalars into ONE
psum of (B + B²) floats per block, bracketed by two VMEM-resident
kernels:

  * ``_gram_kernel`` — stages the block's rows from the resident
    (cols, vals) slice as a (B, k̃_loc) tile and computes the *partial*
    base (B,) and Gram (B, B) for this shard: per row t it walks x_t's
    nonzeros as SMEM scalars (``repro.kernels.dcd_ell``'s lowering) and
    matches each id against the whole tile, so G[:, t] = x_sᵀx_t for
    every s comes out of one lane reduction; base_t is the same row
    walk against the primal shard;
  * caller psums (base, G) over ``model`` — the only collective;
  * ``_update_kernel`` — runs the B-step δ recursion with the same
    ``loss.delta`` family as every other engine (``repro.core.duals``),
    carrying the running α and a δ-history vector: wx_t = base_t +
    δ·G[:, t] (future slots are still 0), then adds δ_t·vals into this
    shard's primal only, nonzero by nonzero.  Repeated row ids (a
    padding-heavy device cycling its valid prefix) are exact: G[s, t] =
    ‖x‖² feeds the earlier δ back in, and α is read from the carried
    output.

Both kernels keep the dummy-slot contract of ``repro.kernels.dcd_ell``:
local padding ids equal d_loc, whose slot in the shard is pinned to 0
by construction (padding ids also match each other in the Gram walk,
but carry value 0).  In exact arithmetic the two-kernel block
is identical to the per-update-psum jnp engine
(``repro.core.sharded._local_block_update_feature``); tests assert
agreement to atol 1e-5.

The two phases are driven through ``repro.kernels.ops`` either eagerly
(``dcd_feature_block_update_pallas``: gram → psum → update per block)
or double-buffered (DESIGN.md §11): the round pipeline keeps the
psummed (base, Gram) of block t in flight across the round boundary —
the gram kernel accepts any *reference* primal shard, and a stale base
is repaired exactly by ``dcd_feature_base_correction`` before the
update kernel consumes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dcd_ell import LANES, load_row, row_axpy, row_dot


def _gram_kernel(
    idx_ref,  # (B, 1)  int32 local row ids of this block (SMEM)
    col_ref,  # (n, k)  shard's local column ids, VMEM-resident
    val_ref,  # (n, k)  shard's values, VMEM-resident
    w_ref,  # (d1/128, 128) this shard's padded primal slice
    base_out,  # (B, 1)  partial w₀ᵀx_t
    gram_out,  # (B, B)  partial Gram x_s·x_t
    cb,  # (B, k) VMEM scratch: the block's column ids
    vb,  # (B, k) VMEM scratch: the block's values
    cbuf,  # (1, k) SMEM scratch: the current row's ids
    vbuf,  # (1, k) SMEM scratch: the current row's values
    sem,  # (2,) DMA semaphores
    *,
    block_rows: int,
):
    # gather the block's rows once into (B, k) VMEM tiles
    def gather(t, carry):
        i = idx_ref[t, 0]
        cb[pl.ds(t, 1), :] = col_ref[pl.ds(i, 1), :]
        vb[pl.ds(t, 1), :] = val_ref[pl.ds(i, 1), :].astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, block_rows, gather, 0)
    cbv, vbv = cb[...], vb[...]
    col_t = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 1)

    def gcol(t, gram):
        load_row(cb, vb, t, cbuf, vbuf, sem)
        base_out[pl.ds(t, 1), :] = row_dot(w_ref, cbuf, vbuf)

        # x_s·x_t for every s at once: walk x_t's nonzeros and match
        # them against the whole block tile (padding ids d_loc match
        # each other, but carry value 0)
        def match(j, acc):
            return acc + jnp.where(cbv == cbuf[0, j], vbv, 0.0) * vbuf[0, j]

        acc = jax.lax.fori_loop(0, cbuf.shape[1], match,
                                jnp.zeros(cbv.shape, jnp.float32))
        col = jnp.sum(acc, axis=1, keepdims=True)  # (B, 1)
        return jnp.where(col_t == t, col, gram)

    gram_out[...] = jax.lax.fori_loop(
        0, block_rows, gcol,
        jnp.zeros((block_rows, block_rows), jnp.float32))


def _update_kernel(
    idx_ref,  # (B, 1)  int32 local row ids (SMEM)
    col_ref,  # (n, k)  shard's local column ids, VMEM-resident
    val_ref,  # (n, k)  shard's values, VMEM-resident
    alpha_ref,  # (n, 1)  duals — seeds the output
    q_ref,  # (n, 1)  FULL row squared norms (summed over shards)
    act_ref,  # (n, 1)  active-set mask (f32 0/1; all-ones = no shrinking)
    y_ref,  # (n, 1)  row labels (±1; all-ones = pre-folded rows)
    w_ref,  # (d1/128, 128) this shard's padded primal slice — seeds w_out
    base_ref,  # (B, 1)  psummed w₀ᵀx_t (UNfolded — y applied below)
    gram_ref,  # (B, B)  psummed Gram (unfolded x_s·x_t)
    alpha_out,  # (n, 1)
    w_out,  # (d1/128, 128)
    cbuf,  # (1, k) SMEM scratch: the current row's ids
    vbuf,  # (1, k) SMEM scratch: the current row's values
    sem,  # (2,) DMA semaphores
    *,
    loss,
    block_rows: int,
):
    alpha_out[...] = alpha_ref[...]
    w_out[...] = w_ref[...]
    gram = gram_ref[...]
    col_t = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 1)
    row_t = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)

    def body(t, deltas):
        # deltas is the FOLDED δ̃_s = δ_s·y_s history (0 ahead): with
        # x̃ = y·x, wᵀx̃_t = y_t·(w₀ᵀx_t + Σ_{s<t} δ_s y_s · x_sᵀx_t),
        # so base and Gram stay unfolded and y enters only here
        i = idx_ref[t, 0]
        load_row(col_ref, val_ref, i, cbuf, vbuf, sem)
        yi = y_ref[pl.ds(i, 1), :]
        # Σ_s δ̃_s·G[s, t]: column t of the Gram, selected by lane mask
        hist = jnp.sum(jnp.where(col_t == t, deltas * gram, 0.0),
                       keepdims=True)
        wx = yi * (base_ref[pl.ds(t, 1), :] + hist)
        a = alpha_out[pl.ds(i, 1), :]  # running α, not the seed
        q = q_ref[pl.ds(i, 1), :]
        # frozen (shrunk) coordinates take the exact zero-delta update;
        # the δ-history then carries a 0, so the Gram recursion and the
        # scatter both see exactly what a skipped row would produce
        delta = jnp.where(
            act_ref[pl.ds(i, 1), :] > 0.0, loss.delta(a, wx, q), 0.0
        )
        alpha_out[pl.ds(i, 1), :] = a + delta
        dtil = delta * yi
        row_axpy(w_out, cbuf, vbuf, dtil)
        return jnp.where(row_t == t, dtil, deltas)

    jax.lax.fori_loop(0, block_rows, body,
                      jnp.zeros((block_rows, 1), jnp.float32))


def _row_scratch(k):
    return [pltpu.SMEM((1, k), jnp.int32), pltpu.SMEM((1, k), jnp.float32),
            pltpu.SemaphoreType.DMA((2,))]


def dcd_feature_gram_pallas_call(
    cols,  # (n, k) int32 local ids, padding == d_loc
    vals,  # (n, k) f32, padding == 0
    w_loc,  # (d1,) this shard's padded primal slice, d1 % 128 == 0
    idx,  # (B,) int32 row ids of the block
    *,
    interpret: bool = False,
):
    """Partial (base, Gram) of one block against this feature shard."""
    n, k = cols.shape
    rows = w_loc.shape[0] // LANES
    b = idx.shape[0]
    kernel = functools.partial(_gram_kernel, block_rows=b)
    base, gram = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, b), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, k), jnp.int32),
                        pltpu.VMEM((b, k), jnp.float32)] + _row_scratch(k),
        interpret=interpret,
    )(idx.reshape(b, 1).astype(jnp.int32), cols, vals,
      w_loc.reshape(rows, LANES).astype(jnp.float32))
    return base.reshape(b), gram


def dcd_feature_update_pallas_call(
    cols,  # (n, k) int32 local ids, padding == d_loc
    vals,  # (n, k) f32
    alpha,  # (n,)
    sq_norms,  # (n,) FULL row norms
    w_loc,  # (d1,) this shard's padded primal slice, d1 % 128 == 0
    idx,  # (B,)
    base,  # (B,)  psummed
    gram,  # (B, B) psummed
    *,
    loss,
    interpret: bool = False,
    active=None,  # (n,) 0/1 active-set mask; None = all active
    y=None,  # (n,) ±1 labels folded on read; None = pre-folded rows
):
    """B sequential δ-recursion updates; scatters only this shard."""
    n, k = cols.shape
    d1 = w_loc.shape[0]
    rows = d1 // LANES
    b = idx.shape[0]
    if active is None:
        act2 = jnp.ones((n, 1), jnp.float32)
    else:
        act2 = active.reshape(n, 1).astype(jnp.float32)
    if y is None:
        y2 = jnp.ones((n, 1), jnp.float32)
    else:
        y2 = y.reshape(n, 1).astype(jnp.float32)
    kernel = functools.partial(_update_kernel, loss=loss, block_rows=b)
    alpha_out, w_out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        scratch_shapes=_row_scratch(k),
        interpret=interpret,
    )(idx.reshape(b, 1).astype(jnp.int32), cols, vals,
      alpha.reshape(n, 1).astype(jnp.float32),
      sq_norms.reshape(n, 1).astype(jnp.float32), act2, y2,
      w_loc.reshape(rows, LANES).astype(jnp.float32),
      base.reshape(b, 1).astype(jnp.float32), gram)
    return alpha_out.reshape(n), w_out.reshape(d1)
