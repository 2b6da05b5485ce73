"""Fixed-shape sparse matrices for XLA.

LIBLINEAR-style datasets (rcv1, webspam, kddb) are CSR with wildly ragged
rows.  XLA wants fixed shapes, so we use the ELL layout: every row is
padded to ``k_max`` nonzeros.  Padding entries use ``index == n_features``
(one past the end) with ``value == 0.0``; consumers keep a ``d+1``-length
scratch vector so padded scatter-adds land in a dummy slot and padded
gathers multiply by zero.  This is also the layout the Pallas DCD kernel
tiles into VMEM (see ``repro/kernels/dcd_block.py``).

Rows whose lengths are heavy-tailed (text corpora) come as a
``CsrMatrix`` instead: no padding, and the 1-D solver packs each device's
rows end to end, each padded only to the walk's granule
(``pack_ragged``).  The paths that take fixed-width rows alone refuse a
``CsrMatrix`` (``refuse_ragged``) rather than pad it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class EllMatrix(NamedTuple):
    """ELL-format sparse matrix with label-folded rows (x_i = y_i * raw_i).

    Attributes:
        indices: (n_rows, k_max) int32 column ids; padding == n_features.
        values:  (n_rows, k_max) float32; padding == 0.
        n_features: static int, true feature dimension d.
    """

    indices: jnp.ndarray
    values: jnp.ndarray
    n_features: int

    @property
    def n_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def k_max(self) -> int:
        return self.indices.shape[1]

    def row_sq_norms(self) -> jnp.ndarray:
        """‖x_i‖² for every row — precomputed once per solve (paper §3.1)."""
        return jnp.sum(self.values * self.values, axis=1)

    def to_dense(self) -> jnp.ndarray:
        d = self.n_features
        dense = jnp.zeros((self.n_rows, d + 1), self.values.dtype)
        rows = jnp.arange(self.n_rows)[:, None]
        dense = dense.at[rows, self.indices].add(self.values)
        return dense[:, :d]


def dense_to_ell(dense, k_max: int | None = None) -> EllMatrix:
    """Convert a dense (n, d) array to ELL (host-side, numpy).

    ``k_max`` defaults to the max per-row nonzero count (≥ 1); forcing it
    larger is allowed (extra slots pad), smaller is an error — truncating
    a row would silently corrupt X, like ``ell_column_split`` it raises.
    """
    dense = np.asarray(dense)
    n, d = dense.shape
    nnz_per_row = (dense != 0).sum(axis=1)
    need = max(int(nnz_per_row.max()) if n else 0, 1)
    if k_max is None:
        k_max = need
    elif k_max < need:
        raise ValueError(f"k_max={k_max} < max per-row nnz {need}")
    indices = np.full((n, k_max), d, dtype=np.int32)
    values = np.zeros((n, k_max), dtype=np.float32)
    for i in range(n):
        (cols,) = np.nonzero(dense[i])
        indices[i, : len(cols)] = cols
        values[i, : len(cols)] = dense[i, cols]
    return EllMatrix(jnp.asarray(indices), jnp.asarray(values), d)


# --------------------------------------------------- ragged rows (CSR) --


class CsrMatrix(NamedTuple):
    """Rows of unequal length in compressed sparse row form, label-folded
    (x_i = y_i·ẋ_i) like ``EllMatrix`` but with no padding: row i holds
    ``indices[indptr[i]:indptr[i+1]]`` and the values beside them.

    Text corpora have heavy-tailed row lengths, so padding every row to
    the longest one (ELL) stores and walks many times the true nonzeros;
    the 1-D solver packs a ``CsrMatrix`` into its own layout instead
    (``pack_ragged``).

    Attributes:
        indices: (nnz,) int32 column ids in [0, n_features).
        values:  (nnz,) float32.
        indptr:  (n_rows + 1,) row offsets, indptr[0] == 0.
        n_features: static int, true feature dimension d.
    """

    indices: np.ndarray
    values: np.ndarray
    indptr: np.ndarray
    n_features: int

    @property
    def n_rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(np.asarray(self.indptr)[-1])

    def row_lengths(self) -> np.ndarray:
        return np.diff(np.asarray(self.indptr))

    def row_of_entry(self) -> np.ndarray:
        """(nnz,) int32 the row each stored entry belongs to."""
        return np.repeat(np.arange(self.n_rows, dtype=np.int32),
                         self.row_lengths())

    def row_sq_norms(self) -> np.ndarray:
        """‖x_i‖² for every row, float32 (host)."""
        v = np.asarray(self.values, np.float32)
        out = np.zeros(self.n_rows, np.float32)
        np.add.at(out, self.row_of_entry(), v * v)
        return out

    def to_ell(self, k_max: int | None = None) -> EllMatrix:
        """The same rows padded to ``k_max`` (default: the longest row,
        ≥ 1) — an ``EllMatrix`` for the paths that take only fixed-width
        rows, at test sizes; a smaller ``k_max`` raises."""
        lens = self.row_lengths()
        need = max(int(lens.max()) if self.n_rows else 0, 1)
        if k_max is None:
            k_max = need
        elif k_max < need:
            raise ValueError(f"k_max={k_max} < max per-row nnz {need}")
        d, n = self.n_features, self.n_rows
        indices = np.full((n, k_max), d, np.int32)
        values = np.zeros((n, k_max), np.float32)
        row = self.row_of_entry()
        col = np.arange(self.nnz) - np.asarray(self.indptr)[:-1][row]
        indices[row, col] = np.asarray(self.indices)
        values[row, col] = np.asarray(self.values)
        return EllMatrix(jnp.asarray(indices), jnp.asarray(values), d)

    def to_dense(self) -> np.ndarray:
        dense = np.zeros((self.n_rows, self.n_features), np.float32)
        np.add.at(dense, (self.row_of_entry(), np.asarray(self.indices)),
                  np.asarray(self.values, np.float32))
        return dense


def csr_from_rows(rows, d: int) -> CsrMatrix:
    """Pack ``[(cols, vals), ...]`` into a ``CsrMatrix`` (host-side); ids
    must lie in [0, d)."""
    d = int(d)
    cols, vals, lens = [], [], []
    for i, (c, v) in enumerate(rows):
        c = np.asarray(c, np.int64).reshape(-1)
        v = np.asarray(v, np.float32).reshape(-1)
        if c.shape[0] != v.shape[0]:
            raise ValueError(
                f"row {i}: {c.shape[0]} ids vs {v.shape[0]} values")
        if c.size and (c.min() < 0 or c.max() >= d):
            raise ValueError(f"row {i}: column id out of range [0, {d})")
        cols.append(c)
        vals.append(v)
        lens.append(c.shape[0])
    indptr = np.concatenate([[0], np.cumsum(lens, dtype=np.int64)])
    return CsrMatrix(
        np.concatenate(cols).astype(np.int32) if cols
        else np.zeros(0, np.int32),
        np.concatenate(vals) if vals else np.zeros(0, np.float32),
        indptr.astype(np.int32), d)


def csr_matvec(mat: CsrMatrix, w: jnp.ndarray) -> jnp.ndarray:
    """X @ w for a (d,) vector, O(nnz). Returns (n_rows,)."""
    row = jnp.asarray(mat.row_of_entry())
    prod = w[jnp.asarray(mat.indices)] * jnp.asarray(mat.values)
    return jnp.zeros((mat.n_rows,), prod.dtype).at[row].add(prod)


def csr_rmatvec(mat: CsrMatrix, alpha: jnp.ndarray) -> jnp.ndarray:
    """Xᵀ @ alpha, O(nnz). Returns (d,)."""
    row = jnp.asarray(mat.row_of_entry())
    vals = jnp.asarray(mat.values)
    return jnp.zeros((mat.n_features,), vals.dtype).at[
        jnp.asarray(mat.indices)].add(alpha[row] * vals)


class PackedRows(NamedTuple):
    """A ``CsrMatrix`` as the 1-D solver holds it on a ``data`` mesh of
    ``n_shards`` devices (host numpy, before placement).  Device j owns
    rows [j·n_loc, (j+1)·n_loc); its rows sit end to end in a segment of
    ``s_loc`` slots, each row padded to a multiple of ``grain`` slots
    (id d, value 0) and the segment to a multiple of ``lanes``, so that
    every device's segment has the same length (SPMD) and a walk of
    ``grain`` slots never crosses into another row.

    Attributes:
        cols: (n_shards·s_loc,) int32 packed column ids; padding == d.
        vals: (n_shards·s_loc,) float32 packed values; padding == 0.
        gseg: (n_shards·s_loc/grain,) int32 the shard-local row of each
            group of ``grain`` slots (the segment's tail: its last row),
            non-decreasing within a segment.
        ptr:  (n_shards·n_loc,) int32 each row's first slot in its
            segment.
        wid:  (n_shards·n_loc,) int32 each row's slots, a multiple of
            ``grain`` (0 for an empty or padding row).
        nnz:  true nonzeros.
    """

    cols: np.ndarray
    vals: np.ndarray
    gseg: np.ndarray
    ptr: np.ndarray
    wid: np.ndarray
    nnz: int

    @property
    def slots(self) -> int:
        """Slots the rows span: what a pass over every row walks."""
        return int(self.wid.sum())


def pack_ragged(mat: CsrMatrix, n_shards: int, n_loc: int, *,
                grain: int = 16, lanes: int = 128) -> PackedRows:
    """Pack ``mat`` into ``PackedRows`` for ``n_shards`` devices of
    ``n_loc`` rows each (n_shards·n_loc ≥ n_rows; the rows past n_rows
    are empty), host-side and vectorised: each entry is copied once."""
    n, d = mat.n_rows, mat.n_features
    n_pad = int(n_shards) * int(n_loc)
    if n_pad < n:
        raise ValueError(f"{n_shards} shards of {n_loc} rows < {n} rows")
    lens = np.zeros(n_pad, np.int64)
    lens[:n] = mat.row_lengths()
    wid = -(-lens // grain) * grain
    per = wid.reshape(n_shards, n_loc)
    ptr = np.cumsum(per, axis=1) - per  # exclusive, per segment
    seg_len = per.sum(axis=1)
    s_loc = max(-(-int(seg_len.max()) // lanes) * lanes, lanes)
    start = (ptr + np.arange(n_shards)[:, None] * s_loc).reshape(-1)
    indptr = np.asarray(mat.indptr, np.int64)
    nnz = int(indptr[-1])
    dest = np.arange(nnz, dtype=np.int64) + np.repeat(
        start[:n] - indptr[:-1], lens[:n])
    cols = np.full(n_shards * s_loc, d, np.int32)
    vals = np.zeros(n_shards * s_loc, np.float32)
    cols[dest] = np.asarray(mat.indices)
    vals[dest] = np.asarray(mat.values)
    groups = s_loc // grain
    gseg = np.empty((n_shards, groups), np.int32)
    for j in range(n_shards):
        g = np.repeat(np.arange(n_loc, dtype=np.int32), per[j] // grain)
        gseg[j, :g.size] = g
        gseg[j, g.size:] = n_loc - 1
    return PackedRows(cols, vals, gseg.reshape(-1),
                      ptr.reshape(-1).astype(np.int32),
                      wid.astype(np.int32), nnz)


# ---------------------------------------------- streaming row append ---


def ell_row_nnz(mat: EllMatrix) -> np.ndarray:
    """Per-row count of real (non-padding) entries, host numpy."""
    return (np.asarray(mat.indices) < mat.n_features).sum(axis=1)


def refuse_ragged(mat, what: str) -> None:
    """Raise if ``mat`` is a ``CsrMatrix``: ``what`` takes fixed-width
    ``EllMatrix`` rows only, and padding heavy-tailed rows to the
    longest one is what the ragged layout exists to avoid."""
    if isinstance(mat, CsrMatrix):
        raise TypeError(
            f"{what} takes fixed-width EllMatrix rows, not a CsrMatrix of "
            f"ragged rows; only the 1-D solve path (prepare_solver on a "
            f"'data' mesh) packs ragged rows without padding them to the "
            f"longest")


def ell_repack(mat: EllMatrix, k_max: int) -> EllMatrix:
    """Re-pack an ``EllMatrix`` to a different ``k_max`` (host-side).

    Real entries are compacted to the front of each row (stable — the
    within-row entry order is preserved) and the tail refilled with the
    ``index == n_features`` / ``value == 0`` sentinel, the same padding
    convention ``pod_row_layout`` uses for whole rows.  Like
    ``dense_to_ell``, shrinking below a row's nonzero count raises —
    truncation would silently corrupt X.
    """
    refuse_ragged(mat, "ell_repack")
    idx = np.asarray(mat.indices)
    val = np.asarray(mat.values)
    n, k = idx.shape
    d = mat.n_features
    k_max = max(int(k_max), 1)
    nnz = (idx < d).sum(axis=1)
    need = int(nnz.max()) if n else 0
    if k_max < need:
        raise ValueError(f"k_max={k_max} < max per-row nnz {need}")
    # stable sort on the padding mask floats real entries to the front
    order = np.argsort(idx >= d, axis=1, kind="stable")
    idx_c = np.take_along_axis(idx, order, axis=1)[:, :min(k, k_max)]
    val_c = np.take_along_axis(val, order, axis=1)[:, :min(k, k_max)]
    out_idx = np.full((n, k_max), d, dtype=np.int32)
    out_val = np.zeros((n, k_max), dtype=np.float32)
    out_idx[:, : idx_c.shape[1]] = idx_c
    out_val[:, : idx_c.shape[1]] = val_c
    return EllMatrix(jnp.asarray(out_idx), jnp.asarray(out_val), d)


def ell_append(mat: EllMatrix, rows: EllMatrix,
               k_max: int | None = None) -> EllMatrix:
    """Append ``rows`` below ``mat`` (host-side) — the streaming-ingest
    path of the serving engine (DESIGN.md §15): fresh labeled rows get
    ELL-packed and stacked under the carried block structure, and the
    warm-start re-solve resumes with the old duals in place and the new
    rows entering at α = 0.

    Both operands must share ``n_features``.  ``k_max`` defaults to
    ``max(mat.k_max, rows.k_max)`` — never lossy; forcing it smaller
    raises inside ``ell_repack`` if any row would truncate.
    """
    refuse_ragged(mat, "ell_append")
    refuse_ragged(rows, "ell_append")
    if rows.n_features != mat.n_features:
        raise ValueError(
            f"n_features mismatch: have {mat.n_features}, "
            f"appending {rows.n_features}")
    if k_max is None:
        k_max = max(mat.k_max, rows.k_max)
    a = ell_repack(mat, k_max)
    b = ell_repack(rows, k_max)
    return EllMatrix(
        jnp.concatenate([a.indices, b.indices], axis=0),
        jnp.concatenate([a.values, b.values], axis=0),
        mat.n_features,
    )


def ell_from_rows(rows, d: int, k_max: int | None = None) -> EllMatrix:
    """Pack a list of sparse rows ``[(cols, vals), ...]`` into an
    ``EllMatrix`` (host-side) without densifying — the request/ingest
    format of the serving engine.

    Every ``cols`` must hold ids in [0, d) matching ``vals`` in length;
    ``k_max`` defaults to the longest row (≥ 1), forcing it smaller
    raises like ``dense_to_ell``.
    """
    d = int(d)
    packed = []
    for i, (cols, vals) in enumerate(rows):
        c = np.asarray(cols, dtype=np.int64).reshape(-1)
        v = np.asarray(vals, dtype=np.float32).reshape(-1)
        if c.shape[0] != v.shape[0]:
            raise ValueError(
                f"row {i}: {c.shape[0]} ids vs {v.shape[0]} values")
        if c.size and (c.min() < 0 or c.max() >= d):
            raise ValueError(f"row {i}: column id out of range [0, {d})")
        packed.append((c, v))
    need = max([len(c) for c, _ in packed], default=0) or 1
    if k_max is None:
        k_max = need
    elif k_max < need:
        raise ValueError(f"k_max={k_max} < max per-row nnz {need}")
    n = len(packed)
    indices = np.full((n, k_max), d, dtype=np.int32)
    values = np.zeros((n, k_max), dtype=np.float32)
    for i, (c, v) in enumerate(packed):
        indices[i, : len(c)] = c
        values[i, : len(c)] = v
    return EllMatrix(jnp.asarray(indices), jnp.asarray(values), d)


def ell_row_dot(mat: EllMatrix, w_pad: jnp.ndarray, i) -> jnp.ndarray:
    """w·x_i against a (d+1,) padded primal vector. O(k_max)."""
    idx = mat.indices[i]
    val = mat.values[i]
    return jnp.sum(w_pad[idx] * val)


def ell_row_axpy(mat: EllMatrix, w_pad: jnp.ndarray, i, scale) -> jnp.ndarray:
    """w += scale * x_i (padded scatter-add; padding lands in slot d)."""
    idx = mat.indices[i]
    val = mat.values[i]
    return w_pad.at[idx].add(scale * val)


def ell_matvec(mat: EllMatrix, w: jnp.ndarray) -> jnp.ndarray:
    """X @ w for a (d,) vector. Returns (n_rows,)."""
    w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
    return jnp.sum(w_pad[mat.indices] * mat.values, axis=1)


def ell_rmatvec(mat: EllMatrix, alpha: jnp.ndarray) -> jnp.ndarray:
    """Xᵀ @ alpha. Returns (d,) — this is w(α) = Σ_i α_i x_i (eq. 3)."""
    d = mat.n_features
    w_pad = jnp.zeros((d + 1,), mat.values.dtype)
    contrib = alpha[:, None] * mat.values
    w_pad = w_pad.at[mat.indices].add(contrib)
    return w_pad[:d]


def pad_primal(w: jnp.ndarray) -> jnp.ndarray:
    """Append the dummy padding slot."""
    return jnp.concatenate([w, jnp.zeros((1,), w.dtype)])


def unpad_primal(w_pad: jnp.ndarray) -> jnp.ndarray:
    return w_pad[:-1]


def active_row_remap(mask: jnp.ndarray):
    """Fixed-capacity compaction of active rows (DESIGN.md §12).

    Returns ``(ids, count)`` where ``ids`` is a length-n int32
    permutation listing the rows with ``mask`` True first — in their
    original order (stable) — and ``count`` is how many there are.  The
    shrinking solver repacks an epoch by drawing its permutation over
    ``[0, count)`` and mapping through ``ids``, so frozen rows stop
    costing update slots while every array keeps its static shape; with
    an all-True mask this is the identity (``ids == arange``), which is
    what makes the repacked path collapse bit-exactly onto the plain one.

    Traceable (no data-dependent shapes): sorting the negated mask is
    stable in jnp, so actives keep their relative order.
    """
    mask = mask.astype(bool)
    ids = jnp.argsort(~mask).astype(jnp.int32)
    return ids, jnp.sum(mask.astype(jnp.int32))


# ---------------------------------------------- row-partitioned ELL ----


def pod_row_layout(n: int, n_pods: int, per_pod_rows: int | None = None):
    """Contiguous row partition across pods (DESIGN.md §13).

    Pod ``k`` owns global rows [k·n_pod_loc, (k+1)·n_pod_loc) with
    ``n_pod_loc = ceil(n / n_pods)``; each pod's slice is padded to
    ``per_pod_rows`` slots (the solver passes p·n_loc so the slice then
    subdivides evenly over the pod's ``data`` devices).  Returns host
    numpy ``(rowmap, mask)``: ``rowmap`` is (n_pods, per_pod_rows) int32
    global row ids with the sentinel ``n`` marking padding slots — a
    gather through it (with a padding row appended at index n) builds
    the pod-sharded layout in one pass — and ``mask = rowmap < n``
    covers exactly the valid rows.  Like ``dense_to_ell``'s ``k_max``,
    forcing ``per_pod_rows`` larger is allowed (extra slots pad),
    smaller is an error — dropping rows would silently corrupt X.
    """
    n = int(n)
    n_pods = int(n_pods)
    if n_pods < 1:
        raise ValueError(f"n_pods must be >= 1, got {n_pods}")
    n_pod_loc = max(-(-n // n_pods), 1)
    if per_pod_rows is None:
        per_pod_rows = n_pod_loc
    elif per_pod_rows < n_pod_loc:
        raise ValueError(
            f"per_pod_rows={per_pod_rows} < rows per pod {n_pod_loc}")
    base = (np.arange(n_pods, dtype=np.int64)[:, None] * n_pod_loc
            + np.arange(per_pod_rows, dtype=np.int64)[None, :])
    mask = (np.arange(per_pod_rows)[None, :]
            < np.clip(n - np.arange(n_pods)[:, None] * n_pod_loc,
                      0, n_pod_loc))
    rowmap = np.where(mask, base, n).astype(np.int32)
    return rowmap, mask


class PodShardedEll(NamedTuple):
    """ELL matrix row-partitioned into ``n_pods`` per-pod shards
    (DESIGN.md §13) — the input layout of the double-async pod solver.

    Pod ``k`` owns the contiguous global row range of
    ``pod_row_layout``; padding slots hold all-padding rows (index ==
    ``n_features``, value 0 — a zero row whose rank-1 update cannot
    move w) and are marked False in ``row_mask``.

    Attributes:
        indices: (n_pods, rows_per_pod, k_max) int32 column ids.
        values:  (n_pods, rows_per_pod, k_max) float32.
        row_mask: (n_pods, rows_per_pod) bool — True exactly on rows
            carrying real data.
        n_features: static int, true feature dimension d.
        n_rows: static int, true global row count n.
    """

    indices: jnp.ndarray
    values: jnp.ndarray
    row_mask: jnp.ndarray
    n_features: int
    n_rows: int

    @property
    def n_pods(self) -> int:
        return self.indices.shape[0]

    @property
    def rows_per_pod(self) -> int:
        return self.indices.shape[1]

    @property
    def k_max(self) -> int:
        return self.indices.shape[2]

    def row_sq_norms(self) -> jnp.ndarray:
        """(n_pods, rows_per_pod) ‖x_i‖² with padding rows forced to 1
        so a (never-selected) padded update's δ stays finite — the same
        q←1 convention as the sharded solver's tail padding."""
        sq = jnp.sum(self.values * self.values, axis=2)
        return jnp.where(self.row_mask, sq, 1.0)

    def to_ell(self) -> EllMatrix:
        """Reassemble the original ``EllMatrix`` — valid rows in (pod,
        slot) order are exactly the original row order, so dropping the
        masked padding is a lossless round-trip (host-side)."""
        idx = np.asarray(self.indices).reshape(-1, self.k_max)
        val = np.asarray(self.values).reshape(-1, self.k_max)
        m = np.asarray(self.row_mask).reshape(-1)
        return EllMatrix(
            jnp.asarray(idx[m]), jnp.asarray(val[m]), self.n_features
        )


def ell_row_partition(mat: EllMatrix, n_pods: int,
                      per_pod_rows: int | None = None) -> PodShardedEll:
    """Partition an ``EllMatrix`` by contiguous row ranges into
    ``n_pods`` per-pod shards (host-side, numpy, one gather — never
    densifies).  The inverse is ``PodShardedEll.to_ell``."""
    rowmap, mask = pod_row_layout(mat.n_rows, n_pods, per_pod_rows)
    d, k = mat.n_features, mat.k_max
    idx = np.concatenate(
        [np.asarray(mat.indices), np.full((1, k), d, np.int32)], axis=0)
    val = np.concatenate(
        [np.asarray(mat.values), np.zeros((1, k), np.float32)], axis=0)
    return PodShardedEll(
        jnp.asarray(idx[rowmap]), jnp.asarray(val[rowmap]),
        jnp.asarray(mask), d, mat.n_rows,
    )


# ------------------------------------------- column-partitioned ELL ----


class FeatureShardedEll(NamedTuple):
    """ELL matrix column-partitioned into ``n_shards`` feature shards.

    Shard ``j`` owns the contiguous global column range
    [j·d_loc, (j+1)·d_loc); every row stores its nonzeros falling in that
    range as a *local* ELL slice, so a device holding only shard j's
    primal slice can gather/scatter with purely local ids (DESIGN.md
    §10).  This is the input layout of the 2D (data × model) solver.

    Attributes:
        indices: (n_rows, n_shards, k_loc) int32 *shard-local* column
            ids (global id − j·d_loc); padding == d_loc, the shard's own
            dummy slot.
        values:  (n_rows, n_shards, k_loc) float32; padding == 0.
        n_features: static int, true global feature dimension d.
        d_loc: static int, features per shard = ceil(d / n_shards).
    """

    indices: jnp.ndarray
    values: jnp.ndarray
    n_features: int
    d_loc: int

    @property
    def n_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def n_shards(self) -> int:
        return self.indices.shape[1]

    @property
    def k_loc(self) -> int:
        return self.indices.shape[2]

    def row_sq_norms(self) -> jnp.ndarray:
        """‖x_i‖² over all shards — identical to the unsplit matrix's."""
        return jnp.sum(self.values * self.values, axis=(1, 2))

    def to_ell(self) -> EllMatrix:
        """Merge back to a single ELL matrix with global column ids
        (k_max = n_shards·k_loc; padding id restored to ``n_features``)."""
        n, m, k = self.indices.shape
        offset = (jnp.arange(m, dtype=jnp.int32) * self.d_loc)[None, :, None]
        glob = jnp.where(
            self.indices >= self.d_loc,
            jnp.int32(self.n_features),
            self.indices + offset,
        )
        return EllMatrix(
            glob.reshape(n, m * k),
            self.values.reshape(n, m * k),
            self.n_features,
        )


def ell_column_split(mat: EllMatrix, n_shards: int,
                     k_loc: int | None = None) -> FeatureShardedEll:
    """Partition an ``EllMatrix`` by contiguous feature ranges into
    ``n_shards`` per-row local ELL slices (host-side, numpy, one pass —
    the data is never densified, which matters at exactly the huge-d
    sizes this layout targets).

    ``k_loc`` defaults to the max per-(row, shard) nonzero count (≥ 1);
    forcing it larger is allowed (extra slots pad), smaller is an error.
    """
    idx = np.asarray(mat.indices)
    val = np.asarray(mat.values)
    n, k = idx.shape
    d = mat.n_features
    m = int(n_shards)
    assert m >= 1
    d_loc = -(-d // m)  # ceil; shard j owns [j*d_loc, (j+1)*d_loc)

    real = idx < d  # padding entries carry id d (one past the end)
    # shard key per entry; padding sorts to a bucket past every shard
    shard = np.where(real, idx // d_loc, m).astype(np.int64)
    order = np.argsort(shard, axis=1, kind="stable")
    shard_s = np.take_along_axis(shard, order, axis=1)
    idx_s = np.take_along_axis(idx, order, axis=1)
    val_s = np.take_along_axis(val, order, axis=1)
    # rank of each entry within its (row, shard) run
    col = np.arange(k, dtype=np.int64)[None, :]
    run_start = shard_s != np.concatenate(
        [np.full((n, 1), -1, np.int64), shard_s[:, :-1]], axis=1
    )
    start_pos = np.maximum.accumulate(np.where(run_start, col, 0), axis=1)
    rank = col - start_pos
    keep = shard_s < m
    need = int(rank[keep].max()) + 1 if keep.any() else 1
    if k_loc is None:
        k_loc = need
    elif k_loc < need:
        raise ValueError(f"k_loc={k_loc} < max per-shard nnz {need}")
    k_loc = max(int(k_loc), 1)

    out_idx = np.full((n, m, k_loc), d_loc, dtype=np.int32)
    out_val = np.zeros((n, m, k_loc), dtype=np.float32)
    rows, cols = np.nonzero(keep)
    j = shard_s[rows, cols]
    out_idx[rows, j, rank[rows, cols]] = (
        idx_s[rows, cols] - j * d_loc
    ).astype(np.int32)
    out_val[rows, j, rank[rows, cols]] = val_s[rows, cols]
    return FeatureShardedEll(
        jnp.asarray(out_idx), jnp.asarray(out_val), d, d_loc
    )
