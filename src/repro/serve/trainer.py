"""Incremental warm-start training for the serving engine
(DESIGN.md §15).

The trainer carries the label-folded training matrix as an
``EllMatrix`` plus the last solve's (α, w).  Fresh labeled rows are
validated and buffered (``add_labeled``); a re-solve (``resolve``)
appends them through ``repro.data.sparse.ell_append`` and dispatches
``solve_segmented`` warm-started from the carried duals — old
coordinates keep their α, appended rows enter at α = 0 via the PR-7
re-blocking, which is why the resumed gap beats a from-scratch solve at
equal epochs.

Robustness: the solve runs under the resilience layer's watchdog, and
the trainer adds an *outer* retry-with-backoff — a ``SolverDiverged``
escape rolls the trainer back to its last healthy (X, α, w) and retries
after an exponential backoff; if every attempt fails, ``resolve``
returns None and the serving path keeps answering from the last
published snapshot.  The drift trigger (``drift_trip``) compares the
published model's error on freshly ingested rows against its error on
the data it was trained on.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from repro.data.labels import ovr_labels
from repro.data.sparse import EllMatrix, ell_append, refuse_ragged
from repro.dist.mesh import drift_trip
from repro.resilience import FaultPlan, SolverDiverged, solve_segmented


def ell_scores(X: EllMatrix, w) -> np.ndarray:
    """Host-side w·x_i for every row of a (label-folded) ELL matrix —
    a correct classification has score > 0."""
    idx = np.asarray(X.indices)
    val = np.asarray(X.values)
    w = np.asarray(w, np.float32).reshape(-1)
    w_pad = np.zeros((X.n_features + 1,), np.float32)
    w_pad[: w.shape[0]] = w[: X.n_features]
    return (w_pad[idx] * val).sum(axis=1)


def fold_labels(rows: EllMatrix, y) -> EllMatrix:
    """Label-fold raw feature rows (x_i ← y_i·x_i) after validating the
    labels the same way the solver mouth does: finite, ±1."""
    y = np.asarray(y, np.float32).reshape(-1)
    if y.shape[0] != rows.n_rows:
        raise ValueError(f"{rows.n_rows} rows but {y.shape[0]} labels")
    if not np.all(np.isfinite(y)):
        raise ValueError("labels must be finite")
    if not np.all(np.abs(y) == 1.0):
        raise ValueError("labels must be +/-1")
    return EllMatrix(rows.indices,
                     np.asarray(rows.values) * y[:, None],
                     rows.n_features)


def _validate_class_ids(y, n_rows: int, n_classes: int) -> np.ndarray:
    """Validate integer class ids the way ``fold_labels`` validates ±1
    labels: right count, integral, in [0, n_classes)."""
    y = np.asarray(y)
    if y.ndim != 1 or y.shape[0] != n_rows:
        raise ValueError(f"{n_rows} rows but labels of shape {y.shape}")
    if not np.issubdtype(y.dtype, np.integer):
        yf = np.asarray(y, np.float64)
        if not np.all(np.isfinite(yf)) or not np.all(yf == np.round(yf)):
            raise ValueError("class ids must be finite integers")
        y = yf.astype(np.int64)
    if y.size and (y.min() < 0 or y.max() >= n_classes):
        raise ValueError(f"class ids must lie in [0, {n_classes})")
    return y.astype(np.int32)


class IncrementalTrainer:
    """Carries (X, α, w) across streaming warm-start re-solves.

    Binary (``n_classes=0``): ``X0`` arrives label-folded, ingested rows
    are folded at admission, α is (n,) and w is (d,).  K-class
    (``n_classes=K``): ``X0`` stays *raw* (shared-X one-vs-rest tasks
    cannot pre-fold), ``y0`` carries the integer class ids, ingested
    rows buffer with their ids, and each re-solve ships the full
    ``ovr_labels`` (K, n) matrix to the multi-task solver — the carried
    α is the (K, n) dual stack, w the (K, d) head stack, and error is
    argmax misclassification.
    """

    def __init__(self, X0: EllMatrix, loss, *, epochs: int = 4,
                 n_classes: int = 0, y0=None,
                 drift_ratio: float = 2.0, drift_floor: float = 0.05,
                 min_new_rows: int = 8, retries: int = 2,
                 backoff_s: float = 0.05,
                 fault_plan: Optional[FaultPlan] = None,
                 solver_kwargs: Optional[dict] = None):
        refuse_ragged(X0, "IncrementalTrainer (ServeEngine's re-solves)")
        self.X = X0
        self.loss = loss
        self.n_classes = int(n_classes)
        if self.n_classes:
            if self.n_classes < 2:
                raise ValueError(
                    f"n_classes must be >= 2 (or 0 for binary), "
                    f"got {n_classes}")
            if y0 is None:
                raise ValueError(
                    "a multiclass trainer needs the class ids of X0")
            self.y_ids = _validate_class_ids(
                y0, X0.n_rows, self.n_classes)
        else:
            if y0 is not None:
                raise ValueError("y0 is only meaningful with n_classes>0")
            self.y_ids = None
        self.epochs = int(epochs)
        self.drift_ratio = float(drift_ratio)
        self.drift_floor = float(drift_floor)
        self.min_new_rows = int(min_new_rows)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.fault_plan = fault_plan
        self.solver_kwargs = dict(solver_kwargs or {})
        self.alpha: Optional[np.ndarray] = None
        self.w: Optional[np.ndarray] = None
        self.err_base: Optional[float] = None
        self._pending: list = []
        self._pending_y: list = []
        self.ledger = {"solves": 0, "diverged": 0, "retries": 0,
                       "gave_up": 0, "drift_trips": 0}

    # ---------------------------------------------------- ingest ----

    @property
    def pending_rows(self) -> int:
        return sum(c.n_rows for c in self._pending)

    def add_labeled(self, rows: EllMatrix, y) -> int:
        """Buffer freshly labeled rows.  Binary: validated +
        label-folded.  Multiclass: rows stay raw and the integer ids
        buffer alongside (folding happens on read inside the solver).
        Returns the pending count."""
        refuse_ragged(rows, "IncrementalTrainer.add_labeled")
        if rows.n_features != self.X.n_features:
            raise ValueError(
                f"n_features mismatch: have {self.X.n_features}, "
                f"got {rows.n_features}")
        if not np.all(np.isfinite(np.asarray(rows.values))):
            raise ValueError("ingested features must be finite")
        if self.n_classes:
            self._pending_y.append(_validate_class_ids(
                y, rows.n_rows, self.n_classes))
            self._pending.append(rows)
        else:
            self._pending.append(fold_labels(rows, y))
        return self.pending_rows

    def _pending_matrix(self) -> Optional[EllMatrix]:
        if not self._pending:
            return None
        merged = self._pending[0]
        for chunk in self._pending[1:]:
            merged = ell_append(merged, chunk)
        return merged

    # ----------------------------------------------------- drift ----

    def error_on(self, X: EllMatrix, w, y_ids=None) -> float:
        """Misclassification fraction of ``w``.  Binary (``y_ids``
        None): folded rows, a correct row scores > 0.  Multiclass: w is
        the (K, d) head stack, a row is correct when its own class wins
        the argmax over per-head margins."""
        if y_ids is None:
            return float(np.mean(ell_scores(X, w) <= 0.0))
        w = np.asarray(w, np.float32)
        margins = np.stack([ell_scores(X, w[k])
                            for k in range(w.shape[0])])  # (K, n)
        return float(np.mean(margins.argmax(axis=0)
                             != np.asarray(y_ids)))

    def drifted(self, w=None) -> bool:
        """Has the stream drifted away from the published model?
        Compares the error on the pending rows against the baseline
        error via ``drift_trip``; needs ``min_new_rows`` pending and an
        established baseline (a solve must have run)."""
        w = self.w if w is None else w
        if w is None or self.err_base is None:
            return False
        if self.pending_rows < self.min_new_rows:
            return False
        pend = self._pending_matrix()
        pend_y = (np.concatenate(self._pending_y)
                  if self.n_classes else None)
        err_new = self.error_on(pend, w, pend_y)
        trip = bool(int(drift_trip(
            np.float32(self.err_base), np.float32(err_new),
            ratio=self.drift_ratio, floor=self.drift_floor)))
        if trip:
            self.ledger["drift_trips"] += 1
        return trip

    # ----------------------------------------------------- solve ----

    def _solve(self, X: EllMatrix, epochs: int, alpha0, w0, plan,
               y_ids=None):
        kw = dict(epochs=epochs, alpha0=alpha0, w0=w0,
                  fault_plan=plan, record=True)
        if y_ids is not None:
            kw["y"] = np.asarray(ovr_labels(y_ids, self.n_classes))
        kw.update(self.solver_kwargs)
        return solve_segmented(X, self.loss, **kw)

    def fit(self, epochs: Optional[int] = None):
        """Initial (or forced full) solve on the carried matrix."""
        return self.resolve(epochs=epochs, require_pending=False)

    def resolve(self, epochs: Optional[int] = None, *,
                require_pending: bool = True):
        """Merge pending rows and warm-start re-solve.  Returns the
        ``ResilientResult`` on success and commits (X, α, w, baseline);
        returns None once the retry budget is exhausted — the carried
        state is untouched and serving continues on the last healthy
        snapshot."""
        if require_pending and not self._pending:
            return None
        epochs = self.epochs if epochs is None else int(epochs)
        pend = self._pending_matrix()
        X_new = self.X if pend is None else ell_append(self.X, pend)
        y_new = None
        if self.n_classes:
            y_new = (self.y_ids if not self._pending_y else
                     np.concatenate([self.y_ids] + self._pending_y))
        plan = self.fault_plan
        for attempt in range(self.retries + 1):
            try:
                res = self._solve(X_new, epochs, self.alpha, self.w,
                                  plan, y_new)
            except SolverDiverged:
                self.ledger["diverged"] += 1
                # transient-fault assumption: disarm a non-persistent
                # plan on retry (its injection already fired); a
                # persistent fault keeps tripping until the budget ends
                if plan is not None and not plan.persistent:
                    plan = None
                if attempt >= self.retries:
                    self.ledger["gave_up"] += 1
                    return None
                self.ledger["retries"] += 1
                time.sleep(self.backoff_s * (2 ** attempt))
                continue
            self.X = X_new
            self.y_ids = y_new
            self.alpha = np.asarray(res.result.alpha)
            self.w = np.asarray(res.result.w_hat)
            self.err_base = self.error_on(self.X, self.w, self.y_ids)
            self._pending = []
            self._pending_y = []
            self.ledger["solves"] += 1
            return res
        return None
