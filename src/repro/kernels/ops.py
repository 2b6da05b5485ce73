"""Jitted wrappers for the Pallas DCD kernels with shape canonicalization.

On a TPU the kernels compile; elsewhere they run in Pallas
``interpret=True`` mode, which checks semantics, not speed."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dcd_block import dcd_epoch_pallas_call
from repro.kernels.dcd_ell import (
    dcd_ell_block_pallas_call,
    dcd_ragged_block_pallas_call,
)
from repro.kernels.dcd_feature import (
    dcd_feature_gram_pallas_call,
    dcd_feature_update_pallas_call,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("c", "sq_hinge", "loss", "block_rows", "interpret"),
)
def _epoch(X, alpha, w, sq_norms, c, sq_hinge, loss, block_rows, interpret):
    return dcd_epoch_pallas_call(
        X, alpha, w, sq_norms,
        c=c, sq_hinge=sq_hinge, loss=loss, block_rows=block_rows,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("c", "sq_hinge", "loss", "block_rows",
                              "interpret"),
)
def _epoch_indexed(X, alpha, w, sq_norms, idx, c, sq_hinge, loss,
                   block_rows, interpret):
    return dcd_epoch_pallas_call(
        X, alpha, w, sq_norms,
        c=c, sq_hinge=sq_hinge, loss=loss, idx=idx, block_rows=block_rows,
        interpret=interpret,
    )


def dcd_epoch_pallas(
    X,
    alpha,
    w,
    sq_norms=None,
    *,
    c: float = 1.0,
    sq_hinge: bool = False,
    loss=None,
    idx=None,
    block_rows: int = 256,
    interpret: bool | None = None,
):
    """One DCD epoch via the Pallas kernel — in row order, or in ``idx``
    order when a row-index vector is given (indexed/gather mode).

    Padding semantics: rows are padded to a block multiple with all-zero
    rows carrying α=0 and q=1, and lanes (d) to a multiple of 128 with
    zero columns.  A zero row cannot change ``w``: its wᵀx is 0 and the
    rank-1 update δ·x is identically zero whatever δ the update rule
    produces.  The q=1 (not the true q=0) only keeps δ finite — e.g. the
    hinge update would otherwise divide (1 − wᵀx) = 1 by the q→1e-12
    safeguard and clip a huge step.  The padding rows' α entries do take
    nonzero junk values (hinge: clip(0 + 1/1, 0, C) = min(1, C)), which
    is why they are sliced off before returning; zero lane-padding
    columns are inert in every dot product and are likewise sliced off
    w.  Net effect: the returned (α[:n], w[:d]) are exactly the unpadded
    epoch's result.

    ``loss`` (any ``repro.core.duals``-style frozen loss) overrides the
    legacy ``c``/``sq_hinge`` flags and extends coverage to logistic.
    ``idx`` (int32 row ids into X) runs the indexed kernel: updates are
    applied in idx order, X stays fully VMEM-resident; out-of-order and
    repeated ids are allowed.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n, d = X.shape
    d_pad = ((d + 127) // 128) * 128
    if sq_norms is None:
        sq_norms = jnp.sum(X * X, axis=1)
    if idx is None:
        br = min(block_rows, max(8, n))
        n_pad = ((n + br - 1) // br) * br
    else:
        idx = jnp.asarray(idx, jnp.int32)
        m = idx.shape[0]
        br = min(block_rows, max(1, m))
        m_pad = ((m + br - 1) // br) * br
        # one extra zero row for padded index slots to land on
        n_pad = n + 1 if m_pad > m else n
        if m_pad > m:
            idx = jnp.concatenate(
                [idx, jnp.full((m_pad - m,), n, jnp.int32)]
            )
    Xp = jnp.zeros((n_pad, d_pad), X.dtype).at[:n, :d].set(X)
    ap = jnp.zeros((n_pad,), jnp.float32).at[:n].set(alpha)
    qp = jnp.ones((n_pad,), jnp.float32).at[:n].set(sq_norms)
    wp = jnp.zeros((d_pad,), jnp.float32).at[:d].set(w)
    if idx is None:
        a_out, w_out = _epoch(Xp, ap, wp, qp, float(c), bool(sq_hinge),
                              loss, br, bool(interpret))
    else:
        a_out, w_out = _epoch_indexed(Xp, ap, wp, qp, idx, float(c),
                                      bool(sq_hinge), loss, br,
                                      bool(interpret))
    return a_out[:n], w_out[:d]


def dcd_block_update_pallas(X, sq_norms, alpha, w, idx, *, loss,
                            interpret: bool = False, active=None,
                            y=None):
    """One indexed block of B sequential DCD updates — the fused
    equivalent of ``repro.core.sharded._local_block_update``.

    Traced (not jitted) so it can run inside a ``shard_map`` body: X is
    this device's (n_loc, d) shard with d already lane-padded to 128 by
    the caller, ``idx`` the (B,) local row ids of the block.  ``active``
    (optional (n_loc,) 0/1 mask) freezes shrunk coordinates to
    zero-delta updates; ``y`` (optional (n_loc,) ±1 labels) folds rows
    on read so multi-task solves can share an unfolded X.  Returns
    (updated α shard, local Δw) exactly like the pure-jnp version.
    """
    a_new, w_new = dcd_epoch_pallas_call(
        X, alpha, w, sq_norms, loss=loss, idx=idx,
        block_rows=idx.shape[0], interpret=interpret, active=active,
        y=y,
    )
    # the full-width Δw is the round merge's (repro.core.sharded)
    with jax.named_scope("passcode.merge"):
        return a_new, w_new - w


def dcd_ell_block_update_pallas(rows, sq_norms, alpha, w_pad, idx, *, k,
                                loss, interpret: bool = False,
                                active=None, y=None):
    """One indexed block of B sequential DCD updates on an ELL shard —
    the fused equivalent of ``repro.core.sharded._local_block_update_ell``.

    Traced (not jitted) so it can run inside a ``shard_map`` body:
    ``rows`` is this device's ELL shard as ``stream_rows`` lays it out
    (left in HBM and streamed row by row; ``k`` the shard's k_max, the
    slots walked per row), ``w_pad`` the (d₁,) padded primal (dummy
    slot at index d, d₁ a multiple of 128), ``idx`` the (B,) local row
    ids of the block.  ``active`` (optional (n_loc,) 0/1 mask) freezes
    shrunk coordinates to zero-delta updates; ``y`` (optional (n_loc,)
    ±1 labels) folds rows on read.  Returns (updated α shard, local
    Δw_pad) exactly like the dense block engine — the padding slots of
    Δw_pad are identically zero.
    """
    a_new, w_new = dcd_ell_block_pallas_call(
        rows, alpha, w_pad, sq_norms, idx, k=k, loss=loss,
        interpret=interpret, active=active, y=y,
    )
    # the full-width Δw is the round merge's (repro.core.sharded)
    with jax.named_scope("passcode.merge"):
        return a_new, w_new - w_pad



def dcd_ragged_block_update_pallas(rows, ptr, wid, sq_norms, alpha, w_pad,
                                   idx, *, loss, interpret: bool = False,
                                   active=None, y=None):
    """``dcd_ell_block_update_pallas`` for packed ragged rows
    (``repro.data.sparse.pack_ragged``): ``rows`` is the packed shard as
    ``ragged_stream_rows`` views it, ``ptr``/``wid`` each local row's
    first slot and slot count.  Each update DMAs and walks only its own
    row.  Returns (updated α shard, local Δw_pad)."""
    a_new, w_new = dcd_ragged_block_pallas_call(
        rows, ptr, wid, alpha, w_pad, sq_norms, idx, loss=loss,
        interpret=interpret, active=active, y=y,
    )
    with jax.named_scope("passcode.merge"):
        return a_new, w_new - w_pad

# ------------------- split-phase 2D (data × model) block entry points ----
# The fused feature-sharded block round is two Pallas kernels bracketing
# ONE ``model``-axis psum (repro.kernels.dcd_feature).  The phases are
# exposed separately so the round pipeline (repro.core.sharded.
# _scan_rounds_overlap, DESIGN.md §11) can keep a block's psummed
# (base, Gram) aggregate in flight while the *next* block's gram kernel
# runs, instead of consuming it immediately.


def dcd_feature_gram_pallas(cols, vals, w_ref, idx, *, axis: str = "model",
                            interpret: bool = False):
    """Phase 1: the block's (base, Gram), psummed over ``axis``.

    ``base`` is w_refᵀx_t against whatever reference primal shard the
    caller holds — the overlapped round passes a shard that is one
    data-round *stale* and restores exactness later via
    ``dcd_feature_base_correction``; the eager round passes the current
    effective shard.  Returns the (B,) base and (B, B) Gram with the
    ``model``-axis partials already reduced — the only collective of the
    fused block."""
    base_p, gram_p = dcd_feature_gram_pallas_call(
        cols, vals, w_ref, idx, interpret=interpret,
    )
    return jax.lax.psum((base_p, gram_p), axis)


def dcd_feature_base_correction(cols, vals, dvec, idx, *,
                                axis: str = "model"):
    """Correct a stale base by the aggregate it was computed without:
    ``Δbase_t = Δwᵀx_t`` for the block's rows, psummed over ``axis``.

    ``dvec`` is this feature shard's slice of the missing aggregate (the
    delayed data-round psum Δw).  An O(B·k̃_loc) gather-dot plus a (B,)
    psum — the only part of the block's read path that must wait for the
    in-flight aggregates, which is what lets the O(B²·k̃_loc) gram kernel
    and the (B + B²)-word psum run ahead, off the critical path."""
    part = jnp.sum(dvec[cols[idx]] * vals[idx], axis=1)
    return jax.lax.psum(part, axis)


def dcd_feature_update_pallas(cols, vals, sq_norms, alpha, w_loc, idx, base,
                              gram, *, loss, interpret: bool = False,
                              active=None, y=None):
    """Phase 2: the B-step δ recursion against a *reduced* (base, Gram);
    no collectives.  ``active`` (optional (n_loc,) 0/1 mask) freezes
    shrunk coordinates to zero-delta updates — legal here because a
    zero δ contributes nothing through the Gram recursion or the
    scatter, so the gram phase needs no mask.  ``y`` (optional (n_loc,)
    ±1 labels) folds rows on read: base and Gram stay unfolded (they
    are y-free, so the gram phase and ``dcd_feature_base_correction``
    need no labels) and the kernel's δ-history carries δ̃ = δ·y.
    Returns (updated α shard, updated primal shard)."""
    return dcd_feature_update_pallas_call(
        cols, vals, alpha, sq_norms, w_loc, idx, base, gram, loss=loss,
        interpret=interpret, active=active, y=y,
    )


def dcd_feature_block_update_pallas(cols, vals, sq_norms, alpha, w_loc, idx,
                                    *, loss, axis: str = "model",
                                    interpret: bool = False,
                                    active=None, y=None):
    """One indexed block of B sequential DCD updates on a 2D
    (data × model) feature shard — the fused equivalent of
    ``repro.core.sharded._local_block_update_feature``; the eager
    (non-overlapped) composition of the split phases above.

    Traced (not jitted) so it runs inside a ``shard_map`` body on a
    ``(data, model)`` mesh: ``cols``/``vals`` are this device's (n_loc,
    k̃_loc) local-id ELL slice, ``w_loc`` its (d₁_loc,) primal *shard*
    (per-shard dummy slot at index d_loc), ``sq_norms`` the FULL row
    norms, ``idx`` the (B,) local row ids of the block.  The per-update
    psum of partial dot products is batched into one psum of the block's
    partial (base, Gram) between two Pallas kernels (see
    ``repro.kernels.dcd_feature``) — exactly equal to the per-update
    rule in exact arithmetic.  Returns (updated α shard, local Δw
    shard)."""
    base, gram = dcd_feature_gram_pallas(
        cols, vals, w_loc, idx, axis=axis, interpret=interpret,
    )
    a_new, w_new = dcd_feature_update_pallas(
        cols, vals, sq_norms, alpha, w_loc, idx, base, gram, loss=loss,
        interpret=interpret, active=active, y=y,
    )
    # the full-width Δw is the round merge's (repro.core.sharded)
    with jax.named_scope("passcode.merge"):
        return a_new, w_new - w_loc
