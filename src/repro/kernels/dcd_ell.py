"""Pallas TPU kernel: ELL (padded-sparse) indexed dual coordinate descent.

Sparse sibling of ``repro.kernels.dcd_block``'s indexed mode (DESIGN.md
§9).  PASSCoDe's datasets are 0.03–1% dense, so the dense kernel's
per-update O(d) dot/axpy and O(n_loc·d) VMEM residency are both ~1000×
larger than the work actually performed.  This kernel keeps the device's
row shard in the ELL layout of ``repro.data.sparse.EllMatrix``:

  cols: (n_loc, k̃) int32 column ids, padding == d (one past the end)
  vals: (n_loc, k̃) f32 values, padding == 0.0

with k̃ = k_max lane-padded to a multiple of 128, and holds ≈ 2·n_loc·k̃
words resident instead of n_loc·d̃ — the VMEM policy is
``repro.dist.mesh.dcd_ell_kernel_fits``.

The padded primal (d₁ = d+1 lane-padded words; slot d is the *dummy
slot*) lives in VMEM as a (d₁/128, 128) ref, so feature c sits at
sublane row c // 128, lane c % 128.  Per update (grid step i, loop step
t over the block's row ids):

  * copy the row's k̃ column ids and values into SMEM (one local DMA
    each), so the walk below reads them as scalars;
  * w·x_i = Σ_j w[c_j]·v_j — per nonzero, one dynamic row slice of the
    primal and a one-hot lane mask, accumulated lane-wise and reduced
    once; padded entries read the dummy slot (0) times value 0;
  * δ via the same ``loss.delta`` as every other engine
    (``repro.core.duals``: closed forms + logistic Newton);
  * w[c_j] += δ·v_j — the same row slice and mask, written back; padding
    ids all land in the dummy slot and add exact zeros, so w[d] stays 0.

Mosaic has no lane gather or scatter into a 1-D value, which is why
each nonzero is a row slice plus a mask rather than ``jnp.take`` /
``.at[].add`` on the primal.

α and w have constant BlockSpec index_maps and the TPU grid executes
sequentially, so both carry across grid steps exactly like the dense
indexed kernel: one pallas_call runs the whole sequence of blocks with
serial-DCD semantics and zero locking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def load_row(col_ref, val_ref, i, cbuf, vbuf, sem):
    """Copy ELL row ``i`` (ids and values) into the SMEM scratch pair."""
    cps = (pltpu.make_async_copy(col_ref.at[pl.ds(i, 1), :], cbuf, sem.at[0]),
           pltpu.make_async_copy(val_ref.at[pl.ds(i, 1), :], vbuf, sem.at[1]))
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()


def row_dot(w_ref, cbuf, vbuf):
    """Σ_j w[c_j]·v_j over the SMEM row against a (d₁/128, 128) primal
    ref — returns a (1, 1) f32."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(j, acc):
        c = cbuf[0, j]
        r = w_ref[pl.ds(c // LANES, 1), :]
        return acc + jnp.where(lane == c % LANES, r, 0.0) * vbuf[0, j]

    acc = jax.lax.fori_loop(0, cbuf.shape[1], body,
                            jnp.zeros((1, LANES), jnp.float32))
    return jnp.sum(acc, axis=1, keepdims=True)


def row_axpy(w_ref, cbuf, vbuf, scale):
    """w[c_j] += scale·v_j over the SMEM row; ``scale`` is (1, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(j, carry):
        c = cbuf[0, j]
        r = pl.ds(c // LANES, 1)
        w_ref[r, :] = w_ref[r, :] + jnp.where(
            lane == c % LANES, scale * vbuf[0, j], 0.0)
        return carry

    jax.lax.fori_loop(0, cbuf.shape[1], body, 0)


def _dcd_ell_indexed_kernel(
    idx_ref,  # (B, 1)  int32 local row ids for this grid step (SMEM)
    col_ref,  # (n, k)  whole shard's column ids, VMEM-resident
    val_ref,  # (n, k)  whole shard's values, VMEM-resident
    alpha_ref,  # (n, 1)  duals — seeds the carried output
    q_ref,  # (n, 1)  row squared norms
    act_ref,  # (n, 1)  active-set mask (f32 0/1; all-ones = no shrinking)
    y_ref,  # (n, 1)  row labels (±1; all-ones = pre-folded rows)
    w_ref,  # (d1/128, 128) padded primal (dummy slot at d) — seeds the carry
    alpha_out,  # (n, 1)  carried across grid steps
    w_out,  # (d1/128, 128) carried across grid steps
    cbuf,  # (1, k) SMEM scratch: the current row's ids
    vbuf,  # (1, k) SMEM scratch: the current row's values
    sem,  # (2,) DMA semaphores
    *,
    loss,
    block_rows: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _seed():
        alpha_out[...] = alpha_ref[...]
        w_out[...] = w_ref[...]

    def body(t, carry):
        i = idx_ref[t, 0]
        load_row(col_ref, val_ref, i, cbuf, vbuf, sem)
        yi = y_ref[pl.ds(i, 1), :]  # (1, 1) ±1 — folds the row on read
        wx = yi * row_dot(w_out, cbuf, vbuf)
        a = alpha_out[pl.ds(i, 1), :]  # running α, not the seed
        q = q_ref[pl.ds(i, 1), :]
        # frozen (shrunk) coordinates take the exact zero-delta update —
        # same gate as the serial reference's masked epoch
        delta = jnp.where(
            act_ref[pl.ds(i, 1), :] > 0.0, loss.delta(a, wx, q), 0.0
        )
        alpha_out[pl.ds(i, 1), :] = a + delta
        # rank-1 sparse axpy; padding ids add δ·0 into the dummy slot
        row_axpy(w_out, cbuf, vbuf, delta * yi)
        return carry

    jax.lax.fori_loop(0, block_rows, body, 0)


def dcd_ell_epoch_pallas_call(
    cols,  # (n, k) int32, k % 128 == 0; padding ids == d (dummy slot)
    vals,  # (n, k) f32, padding == 0
    alpha,  # (n,)
    w_pad,  # (d1,) padded primal, d1 % 128 == 0, slot d and above == 0
    sq_norms,  # (n,)
    *,
    loss,
    idx,  # (m,) int32 row ids, m % block_rows == 0
    block_rows: int = 256,
    interpret: bool = False,
    active=None,  # (n,) 0/1 active-set mask; None = all active
    y=None,  # (n,) ±1 labels folded on read; None = pre-folded rows
):
    n, k = cols.shape
    d1 = w_pad.shape[0]
    rows = d1 // LANES
    m = idx.shape[0]
    assert m % block_rows == 0, (m, block_rows)
    assert d1 % LANES == 0, d1
    grid = (m // block_rows,)
    idx2 = idx.reshape(m, 1).astype(jnp.int32)
    alpha2 = alpha.reshape(n, 1).astype(jnp.float32)
    q2 = sq_norms.reshape(n, 1).astype(jnp.float32)
    if active is None:
        act2 = jnp.ones((n, 1), jnp.float32)
    else:
        act2 = active.reshape(n, 1).astype(jnp.float32)
    if y is None:
        y2 = jnp.ones((n, 1), jnp.float32)
    else:
        y2 = y.reshape(n, 1).astype(jnp.float32)
    w2 = w_pad.reshape(rows, LANES).astype(jnp.float32)
    kernel = functools.partial(
        _dcd_ell_indexed_kernel, loss=loss, block_rows=block_rows
    )
    alpha_out, w_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),  # idx block
            pl.BlockSpec((n, k), lambda i: (0, 0)),  # cols: whole shard
            pl.BlockSpec((n, k), lambda i: (0, 0)),  # vals: whole shard
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # alpha seed
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # sq norms
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # active mask
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # row labels
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),  # w seed
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # carried α
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),  # carried w
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1, k), jnp.int32),
            pltpu.SMEM((1, k), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(idx2, cols, vals, alpha2, q2, act2, y2, w2)
    return alpha_out.reshape(n), w_out.reshape(d1)
