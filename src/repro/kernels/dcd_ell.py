"""Pallas TPU kernel: ELL (padded-sparse) indexed dual coordinate descent.

Sparse sibling of ``repro.kernels.dcd_block``'s indexed mode (DESIGN.md
§9).  PASSCoDe's datasets are 0.03–1% dense, so the dense kernel's
per-update O(d) dot/axpy and O(n_loc·d) VMEM residency are both ~1000×
larger than the work actually performed.  This kernel runs one block of
B sequential updates against the device's row shard in the ELL layout of
``repro.data.sparse.EllMatrix``:

  cols: (n_loc, k) int32 column ids, padding == d (one past the end)
  vals: (n_loc, k) f32 values, padding == 0.0

which the solver keeps at the shard's own width k = k_max.  For the
kernel the shard is copied once per dispatch to an (n_loc, 1, k̃)
layout, k̃ = k lane-padded (``stream_rows``), in which each row is one
lane tile that one DMA moves.  That copy stays in HBM: each row of the
block is streamed into SMEM, the next row's copy in flight while the
current one is walked, and only its k slots are walked — so what is
resident grows with neither n_loc nor k; the VMEM policy is
``repro.dist.mesh.dcd_ell_kernel_fits``.

The padded primal (d₁ = d+1 lane-padded words; slot d is the *dummy
slot*) lives in VMEM as a (d₁/128, 128) ref, so feature c sits at
sublane row c // 128, lane c % 128.  Every id a walk reads lies in
[0, d] (padding is d, the dummy slot), never below 0, so the walks form
that row and lane as ``c >> 7`` and ``c & 127``: the same two numbers
as the signed floor division and remainder, without their divide and
sign corrections on every nonzero.  Per update t of the block:

  * wait for row t's ids and values in SMEM and start row t+1's copy;
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

The per-row scalars — α_i, ‖x_i‖², the active mask and the label — are
gathered for the block's B steps by the caller (B words each, not n_loc)
and the block's α come back per step.  A row the block visits twice
reads the α its earlier visit wrote: ``src[t]`` names the step whose
output holds row t's current α (t itself on a first visit), so the
block keeps the serial-DCD semantics of the jnp engine exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# a feature id c ≥ 0 splits into its primal row c >> LANE_BITS and lane
# c & (LANES - 1): two scalar ops, where c // LANES and c % LANES on a
# signed int32 cost a divide, a remainder and their floor corrections
LANE_BITS = LANES.bit_length() - 1
assert LANES == 1 << LANE_BITS
# Nonzeros walked per loop iteration.  Mosaic lowers a fori_loop either
# rolled or fully unrolled, so the walks unroll by hand.  On a TPU v5e a
# row walk costs 55 ns per nonzero and walk rolled, 11.5 ns unrolled by
# 8; a whole update at rcv1's width takes 2.73, 2.04 and 1.73 µs at 4, 8
# and 16, at news20's 15.5, 11.3 and 9.4 µs (PERF.md §6).
UNROLL = 16


def _walk(n: int, body, carry):
    """``carry = body(j, carry)`` for j in [0, n), ``UNROLL`` steps per
    loop iteration and the remainder unrolled after the loop."""
    def step(jj, carry):
        for u in range(UNROLL):
            carry = body(jj * UNROLL + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, n // UNROLL, step, carry)
    for j in range(n - n % UNROLL, n):
        carry = body(j, carry)
    return carry


def load_row(col_ref, val_ref, i, cbuf, vbuf, sem):
    """Copy ELL row ``i`` (ids and values) into the SMEM scratch pair."""
    cps = (pltpu.make_async_copy(col_ref.at[pl.ds(i, 1), :], cbuf, sem.at[0]),
           pltpu.make_async_copy(val_ref.at[pl.ds(i, 1), :], vbuf, sem.at[1]))
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()


def row_dot(w_ref, cbuf, vbuf, k=None):
    """Σ_j w[c_j]·v_j over the first ``k`` entries (all by default) of
    the (1, k̃) SMEM row pair against a (d₁/128, 128) primal ref —
    returns a (1, 1) f32."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(j, acc):
        c = cbuf[0, j]
        r = w_ref[pl.ds(c >> LANE_BITS, 1), :]
        return acc + jnp.where(lane == c & (LANES - 1), r, 0.0) * vbuf[0, j]

    acc = _walk(cbuf.shape[1] if k is None else k, body,
                jnp.zeros((1, LANES), jnp.float32))
    return jnp.sum(acc, axis=1, keepdims=True)


def row_axpy(w_ref, cbuf, vbuf, scale, k=None):
    """w[c_j] += scale·v_j over the first ``k`` entries of the SMEM row
    pair; ``scale`` is (1, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    scale = jnp.broadcast_to(scale, (1, LANES))  # once, not per nonzero

    def body(j, carry):
        c = cbuf[0, j]
        r = pl.ds(c >> LANE_BITS, 1)
        w_ref[r, :] = w_ref[r, :] + jnp.where(
            lane == c & (LANES - 1), scale * vbuf[0, j], 0.0)
        return carry

    _walk(cbuf.shape[1] if k is None else k, body, 0)


def stream_rows(cols, vals):
    """The shard as the streamed kernel reads it: (n, 1, k̃), k̃ = k
    lane-padded, so that each row is one whole lane tile and one DMA
    (the padding past k is never walked).  Made once per dispatch,
    outside the round loop; the solver's other readers keep the
    unpadded (n, k) shard."""
    n, k = cols.shape
    pad = ((0, 0), (0, -k % LANES))
    return (jnp.pad(cols, pad).reshape(n, 1, -1),
            jnp.pad(vals, pad).reshape(n, 1, -1))


def _row_copies(col_hbm, val_hbm, idx_ref, t, cbuf, vbuf, sem):
    """The DMAs that bring step ``t``'s row into SMEM slot t % 2."""
    i, slot = idx_ref[t], t & 1
    return (pltpu.make_async_copy(col_hbm.at[i], cbuf.at[slot],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(val_hbm.at[i], vbuf.at[slot],
                                  sem.at[1, slot]))


def _dcd_ell_stream_kernel(
    idx_ref,  # (B,)  int32 local row ids of the block's steps (SMEM)
    src_ref,  # (B,)  int32 step whose α output holds row t's α (SMEM)
    row_ref,  # (4, B, 1) per step: α seed, ‖x‖², active (0/1), label ±1
    col_hbm,  # (n, 1, k̃) int32 shard's column ids, left in HBM
    val_hbm,  # (n, 1, k̃) f32 shard's values, left in HBM
    w_ref,  # (d1/128, 128) padded primal (dummy slot at d)
    alpha_out,  # (B, 1) α of step t's row after its update
    w_out,  # (d1/128, 128) updated primal
    cbuf,  # (2, 1, k̃) SMEM: row ids, double-buffered
    vbuf,  # (2, 1, k̃) SMEM: row values, double-buffered
    sem,  # (2, 2) DMA semaphores: (ids|values, slot)
    *,
    loss,
    k: int,
    block_rows: int,
):
    for cp in _row_copies(col_hbm, val_hbm, idx_ref, 0, cbuf, vbuf, sem):
        cp.start()
    alpha_out[...] = row_ref[0]
    w_out[...] = w_ref[...]

    def body(t, carry):
        for cp in _row_copies(col_hbm, val_hbm, idx_ref, t, cbuf, vbuf,
                              sem):
            cp.wait()

        @pl.when(t + 1 < block_rows)
        def _prefetch():
            for cp in _row_copies(col_hbm, val_hbm, idx_ref, t + 1, cbuf,
                                  vbuf, sem):
                cp.start()

        cb, vb = cbuf.at[t & 1], vbuf.at[t & 1]
        step = pl.ds(t, 1)
        yi = row_ref[3, step, :]  # (1, 1) ±1 — folds the row on read
        wx = yi * row_dot(w_out, cb, vb, k)
        a = alpha_out[pl.ds(src_ref[t], 1), :]  # running α, not the seed
        # frozen (shrunk) coordinates take the exact zero-delta update —
        # same gate as the serial reference's masked epoch
        delta = jnp.where(row_ref[2, step, :] > 0.0,
                          loss.delta(a, wx, row_ref[1, step, :]), 0.0)
        alpha_out[step, :] = a + delta
        # rank-1 sparse axpy; padding ids add δ·0 into the dummy slot
        row_axpy(w_out, cb, vb, delta * yi, k)
        return carry

    jax.lax.fori_loop(0, block_rows, body, 0)


def dcd_ell_block_pallas_call(
    rows,  # stream_rows(cols, vals): (n, 1, k̃) ids (padding == d), values
    alpha,  # (n,)
    w_pad,  # (d1,) padded primal, d1 % 128 == 0, slot d and above == 0
    sq_norms,  # (n,)
    idx,  # (B,) int32 local row ids of the block, repeats allowed
    *,
    k: int,  # slots walked per row: the shard's k_max
    loss,
    interpret: bool = False,
    active=None,  # (n,) 0/1 active-set mask; None = all active
    y=None,  # (n,) ±1 labels folded on read; None = pre-folded rows
):
    """B sequential DCD updates in ``idx`` order; returns (α, w_pad)."""
    cols, vals = rows
    kp = cols.shape[-1]
    d1 = w_pad.shape[0]
    b = idx.shape[0]
    assert d1 % LANES == 0 and kp % LANES == 0 and k <= kp, (d1, kp, k)
    idx = idx.astype(jnp.int32)
    # the step of each row's previous visit in the block (itself on a
    # first visit) and of its last visit, whose α is the block's answer
    step = jnp.arange(b)
    same = idx[:, None] == idx[None, :]
    prev = jnp.max(jnp.where(same & (step[None, :] < step[:, None]),
                             step[None, :], -1), axis=1)
    src = jnp.where(prev < 0, step, prev)
    last = jnp.max(jnp.where(same, step[None, :], 0), axis=1)
    ones = jnp.ones((b,), jnp.float32)
    per_step = jnp.stack([
        alpha[idx], sq_norms[idx],
        ones if active is None else active[idx].astype(jnp.float32),
        ones if y is None else y[idx].astype(jnp.float32),
    ]).astype(jnp.float32)[:, :, None]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    a_steps, w_out = pl.pallas_call(
        functools.partial(_dcd_ell_stream_kernel, loss=loss, k=k,
                          block_rows=b),
        in_specs=[smem, smem, vmem, hbm, hbm, vmem],
        out_specs=[vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((d1 // LANES, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2, 1, kp), jnp.int32),
            pltpu.SMEM((2, 1, kp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(idx, src.astype(jnp.int32), per_step, cols, vals,
      w_pad.reshape(-1, LANES).astype(jnp.float32))
    # a row visited twice gets the same (last) α from both visits
    alpha = alpha.at[idx].set(a_steps[last, 0])
    return alpha, w_out.reshape(d1)


# ----------------------------------------------------- ragged rows ----
# Rows of unequal length (``repro.data.sparse.CsrMatrix``), packed end to
# end on the device with each row padded to a multiple of ``GRAIN``
# slots (``pack_ragged``).  Each step DMAs only the lane tiles its row
# spans and walks only the row's own slots; a row longer than one SMEM
# slot of ``CHUNK_TILES`` tiles is streamed in chunks.

GRAIN = UNROLL  # slots per walk group; a packed row is a multiple of it
GROUPS_PER_TILE = LANES // GRAIN
GROUP_BITS = GROUPS_PER_TILE.bit_length() - 1
assert GROUPS_PER_TILE == 1 << GROUP_BITS
# lane tiles per SMEM row slot: 512 words, the slot news20's 455-wide
# rows already take; two slots of ids and values are 8 KiB of SMEM
CHUNK_TILES = 4


def _ragged_dot(w_ref, cbuf, vbuf, base, lo, hi, acc):
    """``acc`` + Σ w[c_j]·v_j over the groups [lo, hi) of the chunk whose
    first tile is ``base`` in the SMEM buffer pair; acc is (1, 128)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def group(g, acc):
        tile = base + (g >> GROUP_BITS)
        l0 = (g & (GROUPS_PER_TILE - 1)) * GRAIN
        cb, vb = cbuf.at[tile], vbuf.at[tile]
        for u in range(GRAIN):
            c = cb[0, l0 + u]
            r = w_ref[pl.ds(c >> LANE_BITS, 1), :]
            acc = acc + jnp.where(lane == c & (LANES - 1), r, 0.0) * vb[
                0, l0 + u]
        return acc

    return jax.lax.fori_loop(lo, hi, group, acc)


def _ragged_axpy(w_ref, cbuf, vbuf, base, lo, hi, scale):
    """w[c_j] += scale·v_j over the groups [lo, hi) of one chunk."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    scale = jnp.broadcast_to(scale, (1, LANES))  # once, not per slot

    def group(g, carry):
        tile = base + (g >> GROUP_BITS)
        l0 = (g & (GROUPS_PER_TILE - 1)) * GRAIN
        cb, vb = cbuf.at[tile], vbuf.at[tile]
        for u in range(GRAIN):
            c = cb[0, l0 + u]
            r = pl.ds(c >> LANE_BITS, 1)
            w_ref[r, :] = w_ref[r, :] + jnp.where(
                lane == c & (LANES - 1), scale * vb[0, l0 + u], 0.0)
        return carry

    jax.lax.fori_loop(lo, hi, group, 0)


def _dcd_ragged_stream_kernel(
    tile_ref,  # (B,) int32 first lane tile of step t's row (SMEM)
    off_ref,  # (B,) int32 the row's first slot within that tile (SMEM)
    wid_ref,  # (B,) int32 the row's slots, a multiple of GRAIN (SMEM)
    src_ref,  # (B,) int32 step whose α output holds row t's α (SMEM)
    row_ref,  # (4, B, 1) per step: α seed, ‖x‖², active (0/1), label ±1
    col_hbm,  # (T, 1, 128) int32 packed column ids, left in HBM
    val_hbm,  # (T, 1, 128) f32 packed values, left in HBM
    w_ref,  # (d1/128, 128) padded primal (dummy slot at d)
    alpha_out,  # (B, 1) α of step t's row after its update
    w_out,  # (d1/128, 128) updated primal
    cbuf,  # (2·CHUNK_TILES, 1, 128) SMEM: two chunk slots of ids
    vbuf,  # (2·CHUNK_TILES, 1, 128) SMEM: two chunk slots of values
    sem,  # (2, 2) DMA semaphores: (ids|values, slot)
    *,
    loss,
    block_rows: int,
):
    span = CHUNK_TILES * LANES  # slots per chunk

    def n_tiles(t):
        return (off_ref[t] + wid_ref[t] + LANES - 1) // LANES

    def copies(t, chunk, slot, j):
        i = tile_ref[t] + chunk * CHUNK_TILES + j
        dst = pl.ds(slot * CHUNK_TILES + j, 1)
        return (pltpu.make_async_copy(col_hbm.at[pl.ds(i, 1)], cbuf.at[dst],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(val_hbm.at[pl.ds(i, 1)], vbuf.at[dst],
                                      sem.at[1, slot]))

    def dma(t, chunk, slot, op):
        """Start (or wait for) the tile copies of chunk ``chunk`` of step
        t's row into SMEM slot ``slot``: only the tiles the row spans."""
        def one(j, carry):
            for cp in copies(t, chunk, slot, j):
                getattr(cp, op)()
            return carry

        n = jnp.clip(n_tiles(t) - chunk * CHUNK_TILES, 0, CHUNK_TILES)
        jax.lax.fori_loop(0, n, one, 0)

    def stream(t, slot, n_chunks, walk, carry):
        """``walk`` over the chunks of step t's row, chunk c in slot
        (slot + c) % 2 (chunk 0 already there), the next chunk's copy in
        flight while the current one is walked."""
        lo0, hi0 = off_ref[t], off_ref[t] + wid_ref[t]

        def chunk(c, carry):
            s = (slot + c) % 2
            more = c + 1 < n_chunks

            @pl.when(more)
            def _next():
                dma(t, c + 1, 1 - s, "start")

            lo = jnp.maximum(lo0 - c * span, 0) // GRAIN
            hi = jnp.clip(hi0 - c * span, 0, span) // GRAIN
            carry = walk(s * CHUNK_TILES, lo, hi, carry)

            @pl.when(more)
            def _arrived():
                dma(t, c + 1, 1 - s, "wait")

            return carry

        return jax.lax.fori_loop(0, n_chunks, chunk, carry)

    dma(0, 0, 0, "start")
    alpha_out[...] = row_ref[0]
    w_out[...] = w_ref[...]

    def body(t, carry):
        slot = t % 2
        n_chunks = jnp.maximum(
            (n_tiles(t) + CHUNK_TILES - 1) // CHUNK_TILES, 1)
        long = n_chunks > 1
        dma(t, 0, slot, "wait")

        # a row of one chunk leaves the other slot free for the next
        # row's copy now; a longer row takes both slots, and the next
        # row's copy starts once it is done
        @pl.when(jnp.logical_and(t + 1 < block_rows,
                                 jnp.logical_not(long)))
        def _prefetch():
            dma(t + 1, 0, 1 - slot, "start")

        step = pl.ds(t, 1)
        yi = row_ref[3, step, :]  # (1, 1) ±1 — folds the row on read
        acc = stream(
            t, slot, n_chunks,
            lambda base, lo, hi, acc: _ragged_dot(w_out, cbuf, vbuf, base,
                                                  lo, hi, acc),
            jnp.zeros((1, LANES), jnp.float32))
        wx = yi * jnp.sum(acc, axis=1, keepdims=True)
        a = alpha_out[pl.ds(src_ref[t], 1), :]  # running α, not the seed
        delta = jnp.where(row_ref[2, step, :] > 0.0,
                          loss.delta(a, wx, row_ref[1, step, :]), 0.0)
        alpha_out[step, :] = a + delta
        scale = delta * yi

        # a streamed row's first chunk was overwritten: fetch it again
        @pl.when(long)
        def _again():
            dma(t, 0, slot, "start")
            dma(t, 0, slot, "wait")

        def axpy(base, lo, hi, carry):
            _ragged_axpy(w_out, cbuf, vbuf, base, lo, hi, scale)
            return carry

        stream(t, slot, n_chunks, axpy, 0)

        @pl.when(jnp.logical_and(t + 1 < block_rows, long))
        def _prefetch_late():
            dma(t + 1, 0, 1 - slot, "start")

        return carry

    jax.lax.fori_loop(0, block_rows, body, 0)


def ragged_stream_rows(cols, vals):
    """A packed shard as the ragged kernel reads it: the flat (S,) slots,
    S a multiple of 128, viewed as (S/128, 1, 128) lane tiles."""
    return cols.reshape(-1, 1, LANES), vals.reshape(-1, 1, LANES)


def dcd_ragged_block_pallas_call(
    rows,  # ragged_stream_rows(cols, vals): (T, 1, 128) ids, values
    ptr,  # (n,) int32 first slot of each local row in the packed shard
    wid,  # (n,) int32 slots of each row, a multiple of GRAIN
    alpha,  # (n,)
    w_pad,  # (d1,) padded primal, d1 % 128 == 0, slot d and above == 0
    sq_norms,  # (n,)
    idx,  # (B,) int32 local row ids of the block, repeats allowed
    *,
    loss,
    interpret: bool = False,
    active=None,  # (n,) 0/1 active-set mask; None = all active
    y=None,  # (n,) ±1 labels folded on read; None = pre-folded rows
):
    """B sequential DCD updates over packed ragged rows in ``idx``
    order; returns (α, w_pad).  The block semantics (``src``, the last
    visit's α) are ``dcd_ell_block_pallas_call``'s."""
    cols, vals = rows
    d1 = w_pad.shape[0]
    b = idx.shape[0]
    assert d1 % LANES == 0 and cols.shape[1:] == (1, LANES), (d1, cols.shape)
    idx = idx.astype(jnp.int32)
    step = jnp.arange(b)
    same = idx[:, None] == idx[None, :]
    prev = jnp.max(jnp.where(same & (step[None, :] < step[:, None]),
                             step[None, :], -1), axis=1)
    src = jnp.where(prev < 0, step, prev)
    last = jnp.max(jnp.where(same, step[None, :], 0), axis=1)
    ones = jnp.ones((b,), jnp.float32)
    per_step = jnp.stack([
        alpha[idx], sq_norms[idx],
        ones if active is None else active[idx].astype(jnp.float32),
        ones if y is None else y[idx].astype(jnp.float32),
    ]).astype(jnp.float32)[:, :, None]
    start = ptr[idx].astype(jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    a_steps, w_out = pl.pallas_call(
        functools.partial(_dcd_ragged_stream_kernel, loss=loss,
                          block_rows=b),
        in_specs=[smem, smem, smem, smem, vmem, hbm, hbm, vmem],
        out_specs=[vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((d1 // LANES, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((2 * CHUNK_TILES, 1, LANES), jnp.int32),
            pltpu.SMEM((2 * CHUNK_TILES, 1, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
    )(start // LANES, start % LANES, wid[idx].astype(jnp.int32),
      src.astype(jnp.int32), per_step, cols, vals,
      w_pad.reshape(-1, LANES).astype(jnp.float32))
    alpha = alpha.at[idx].set(a_steps[last, 0])
    return alpha, w_out.reshape(d1)
