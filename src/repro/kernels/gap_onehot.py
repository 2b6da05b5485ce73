"""Pallas TPU kernels: the duality gap's two sparse products on the MXU.

The per-epoch gap (``repro.core.sharded._gap_1d_from``) forms
w(α) = Xᵀ(α) (``rmv``) and the margins X·w(α) (``mv``) over every stored
entry of the shard.  As XLA ops these are a scatter-add and a gather of
one word per entry, which run at a few ns per entry.  Here both run as
dense matmuls against a primal that stays in VMEM (DESIGN.md §17).

Each column id splits as c = 128·hi + lo, so the d_run-word primal is an
(R, 128) tile, R = d_run / 128, padded to R̃ = R rounded up to 128.  For
a row vector of L entries (ids c_e, values u_e):

  * scatter: W[hi, lo] += Σ_e u_e·[hi_e = hi]·[lo_e = lo] = (Aᵀ Bᵀᵀ),
    Aᵀ (R̃ × L) one-hot in hi, Bᵀ (128 × L) one-hot in lo carrying u;
  * gather: W[hi_e, lo_e] = Σ_lo [lo_e = lo]·(Wᵀ Aᵀ)[lo, e], a
    (128 × R̃)·(R̃ × L) product, a select and a sum over sublanes.

The one-hot operands are exact in bfloat16.  The f32 operand (u, or W)
splits into three bfloat16 parts whose sum is the f32 value, and every
product accumulates in f32, so both products are exact to f32 rounding:
a gathered word is the stored word, a scattered sum differs from XLA's
only in the order of its additions.  Padding ids (the dummy slot d,
value 0) add exact zeros, as they do in the XLA ops.

The entries stream from HBM in blocks of L rows of the stored (N, k)
pair (the ELL shard as placed, or the packed ragged slots viewed as
(N, 128)); each block is k row vectors of L entries, one per stored
row, read as stored where XLA keeps the pair column-major (a narrow ELL
shard) and transposed in VMEM otherwise, so the shard is never copied.
``scale`` multiplies row i's values by a_i (ELL: α, so that u = α_i·v_ij
is formed in VMEM), and ``row_sum`` sums the gathered products over each
stored row (ELL: the margins).

A product costs 2·R̃·128·3 flops per entry, so the pass pays only while
R is small: ``gap_onehot_fits`` admits R ≤ ``R_MAX``, the crossover
against the XLA ops measured on a TPU v5e (PERF.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# largest R = d_run / 128 the pass is admitted at: on a TPU v5e its time
# per entry grows with R (3.5 ns for both products at R = 370, 13.4 at
# 1,536, 17.4 at 2,048) while the XLA scatter and gather take 14.1 at
# every R; 1,536 is the widest measured R where the pass is faster
R_MAX = 1536
# elements of an (R̃, L) one-hot: L = this / R̃, in [128, 1024], keeps the
# operands and their f32 temporaries a few MiB of VMEM at any admitted R
_ONEHOT_ELEMS = 1 << 19
_SPLIT = 3  # bfloat16 parts of an f32 operand


def gap_onehot_fits(d_run: int) -> bool:
    """Whether the one-hot pass serves a d_run-word primal (d_run a
    multiple of 128, as the fused engines lay it out)."""
    return d_run % LANES == 0 and d_run // LANES <= R_MAX


def _r_pad(d_run: int) -> int:
    return -(-(d_run // LANES) // LANES) * LANES


def _block_rows(r_pad: int) -> int:
    """Stored rows per grid step: the lane width L of the one-hots, a
    power of two in [128, 1024]."""
    fit = max(_ONEHOT_ELEMS // r_pad, LANES)
    return min(1 << (fit.bit_length() - 1), 1024)


def _split3(x):
    """Three bfloat16 parts of the f32 ``x`` whose f32 sum is ``x``."""
    p0 = x.astype(jnp.bfloat16)
    r = x - p0.astype(jnp.float32)
    p1 = r.astype(jnp.bfloat16)
    return p0, p1, (r - p1.astype(jnp.float32)).astype(jnp.bfloat16)


def _hi_onehot(c, r_pad: int):
    """(R̃, L) bfloat16: [c_e // 128 = hi] for the (1, L) ids ``c``."""
    hi = jax.lax.broadcasted_iota(jnp.int32, (r_pad, c.shape[1]), 0)
    return jnp.where(hi == c // LANES, 1.0, 0.0).astype(jnp.bfloat16)


def _lo_mask(c):
    """(128, L) bool: [c_e % 128 = lo] for the (1, L) ids ``c``."""
    lo = jax.lax.broadcasted_iota(jnp.int32, (LANES, c.shape[1]), 0)
    return lo == c % LANES


def _scatter_row(c, u, r_pad: int):
    """(R̃, 128) f32: the row vector's Σ u_e at each (hi_e, lo_e)."""
    lo = _lo_mask(c)
    bt = jnp.concatenate(
        [jnp.where(lo, part.astype(jnp.float32), 0.0).astype(jnp.bfloat16)
         for part in _split3(u)], axis=0)  # (3·128, L)
    p = jax.lax.dot_general(_hi_onehot(c, r_pad), bt,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return p[:, :LANES] + p[:, LANES:2 * LANES] + p[:, 2 * LANES:]


def _gather_row(wt_ref, c, r_pad: int):
    """(1, L) f32: W[c_e] for the (1, L) ids ``c``; ``wt_ref`` holds Wᵀ
    as three stacked bfloat16 parts, (3·128, R̃), formed in the kernel."""
    p = jnp.dot(wt_ref[...], _hi_onehot(c, r_pad),
                preferred_element_type=jnp.float32)  # (3·128, L)
    q = p[:LANES] + p[LANES:2 * LANES] + p[2 * LANES:]  # W[hi_e, :]
    return jnp.sum(jnp.where(_lo_mask(c), q, 0.0), axis=0, keepdims=True)


def _stored_lane_major(n: int, k: int) -> bool:
    """Whether the TPU keeps an (n, k) array column-major, as (k, n) row
    vectors: XLA's default layout pads each dimension to its tile, 8
    sublanes by 128 lanes, and takes the order that pads less (rcv1's
    (n, 73) shard: (80, n) rather than (n, 128)).  The passes then read
    the stored layout as it is; the other order is transposed in VMEM."""
    def pad(x, m):
        return -(-x // m) * m
    return pad(k, 8) * pad(n, LANES) < pad(n, 8) * pad(k, LANES)


def _to_rows(ref, lane_major: bool):
    """A block as row vectors of entries: (k, L)."""
    return ref[...] if lane_major else ref[...].T


def _scatter_kernel(s_ref, c_ref, v_ref, w_ref, cs, us, ss, *, n_rows: int,
                    k: int, r_pad: int, lane_major: bool):
    i = pl.program_id(0)
    tb = cs.shape[1]

    @pl.when(i == 0)
    def _zero():
        w_ref[...] = jnp.zeros_like(w_ref)

    # the last block runs past the stored rows: its lanes there add 0
    live = i * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1) < n_rows
    cs[...] = _to_rows(c_ref, lane_major)
    us[...] = jnp.where(live, _to_rows(v_ref, lane_major), 0.0)
    ss[...] = jnp.where(live, s_ref[...], 0.0)
    per = k // ss.shape[0]  # entries of a stored row per scale

    def body(j, carry):
        u = us[pl.ds(j, 1), :] * ss[pl.ds(j // per, 1), :]
        w_ref[...] += _scatter_row(cs[pl.ds(j, 1), :], u, r_pad)
        return carry

    jax.lax.fori_loop(0, k, body, 0)


def _gather_kernel(w_ref, c_ref, v_ref, z_ref, wt, cs, vs, *, k: int,
                   r_pad: int, row_sum: bool, lane_major: bool):
    # Wᵀ's three parts are formed here, once per call: outside the
    # kernel XLA's TPU simplifier drops the f32 → bfloat16 → f32 round
    # trip of the split (excess precision), leaving W at bfloat16
    @pl.when(pl.program_id(0) == 0)
    def _split():
        wt[...] = jnp.concatenate(_split3(w_ref[...].T), axis=0)

    cs[...] = _to_rows(c_ref, lane_major)
    vs[...] = _to_rows(v_ref, lane_major)
    tb = cs.shape[1]

    def prod(j):
        return (_gather_row(wt, cs[pl.ds(j, 1), :], r_pad)
                * vs[pl.ds(j, 1), :])

    if row_sum:
        z_ref[...] = jax.lax.fori_loop(
            0, k, lambda j, z: z + prod(j),
            jnp.zeros((1, tb), jnp.float32))
        return

    def body(j, carry):
        vs[pl.ds(j, 1), :] = prod(j)
        return carry

    jax.lax.fori_loop(0, k, body, 0)
    z_ref[...] = vs[...] if lane_major else vs[...].T


def _entry_specs(n: int, k: int, tb: int):
    """(lane_major, BlockSpec of a block of tb stored rows, operand
    view): the stored pair is passed as the TPU keeps it."""
    if _stored_lane_major(n, k):
        return True, pl.BlockSpec((k, tb), lambda i: (0, i)), jnp.transpose
    return False, pl.BlockSpec((tb, k), lambda i: (i, 0)), lambda x: x


def onehot_rmv(cols, vals, d_run: int, scale, *, interpret: bool = False):
    """w (d_run,) f32 with w[c] = Σ over the stored entries with id c of
    s_ij·v_ij.  ``cols``/``vals`` (N, k) int32/f32, ids in [0, d_run).
    ``scale`` is (N,), s_ij = a_i, or (N, G), G dividing k,
    s_ij = a_i,(j·G // k): one word per stored row (ELL: α) or per group
    of k/G entries (packed slots: each row's α per group of ``GRAIN``
    slots), multiplied in VMEM."""
    n, k = cols.shape
    r_pad = _r_pad(d_run)
    tb = _block_rows(r_pad)
    lane_major, rows, view = _entry_specs(n, k, tb)
    c, v = view(cols), view(vals.astype(jnp.float32))
    scale = scale.astype(jnp.float32).reshape(n, -1).T  # (G, N)
    g = scale.shape[0]
    assert k % g == 0, (k, g)
    w = pl.pallas_call(
        functools.partial(_scatter_kernel, n_rows=n, k=k, r_pad=r_pad,
                          lane_major=lane_major),
        grid=(pl.cdiv(n, tb),),
        in_specs=[pl.BlockSpec((g, tb), lambda i: (0, i)), rows, rows],
        out_specs=pl.BlockSpec((r_pad, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, tb), jnp.int32),
                        pltpu.VMEM((k, tb), jnp.float32),
                        pltpu.VMEM((g, tb), jnp.float32)],
        interpret=interpret,
    )(scale, c, v)
    return w[:d_run // LANES].reshape(d_run)


def onehot_mv(cols, vals, w, *, row_sum: bool, interpret: bool = False):
    """The stored entries' products w[c_ij]·v_ij: summed over each
    stored row into (N,) when ``row_sum``, else (N, k).  ``w`` is the
    (d_run,) f32 primal, d_run a multiple of 128."""
    n, k = cols.shape
    d_run = w.shape[0]
    r_pad = _r_pad(d_run)
    tb = _block_rows(r_pad)
    lane_major, rows, view = _entry_specs(n, k, tb)
    w = jnp.zeros((r_pad, LANES), jnp.float32).at[:d_run // LANES].set(
        w.reshape(-1, LANES))
    if row_sum:
        out_spec = pl.BlockSpec((1, tb), lambda i: (0, i))
        out_shape = jax.ShapeDtypeStruct((1, n), jnp.float32)
    else:
        out_spec = rows
        out_shape = jax.ShapeDtypeStruct(
            (k, n) if lane_major else (n, k), jnp.float32)
    z = pl.pallas_call(
        functools.partial(_gather_kernel, k=k, r_pad=r_pad,
                          row_sum=row_sum, lane_major=lane_major),
        grid=(pl.cdiv(n, tb),),
        in_specs=[pl.BlockSpec((r_pad, LANES), lambda i: (0, 0)),
                  rows, rows],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_SPLIT * LANES, r_pad), jnp.bfloat16),
                        pltpu.VMEM((k, tb), jnp.int32),
                        pltpu.VMEM((k, tb), jnp.float32)],
        interpret=interpret,
    )(w, view(cols), view(vals.astype(jnp.float32)))
    return z.reshape(n) if row_sum else view(z)
