"""Segmented fault-tolerant solve (DESIGN.md §14).

``solve_segmented`` is the resilient variant of
``sharded_passcode_solve``: the same prepared setup and the same update
sequence, but dispatched in ``checkpoint_every``-epoch segments around
the epoch scan.  Because the segmented pipeline carries the FULL solver
state (``pipeline_state_keys``) and the scan keys every epoch-dependent
decision on the *global* epoch, a segmented run is bit-identical to the
whole-solve dispatch — which is what makes the recovery story exact:

  * each segment boundary optionally persists the state via
    ``repro.train.checkpoint`` (atomic, content-hashed); a killed
    process resumes from the last boundary and replays bit-for-bit;
  * the on-device watchdog (carried ``health`` code) is read back once
    per segment — a trip rolls back to the in-memory snapshot of the
    last healthy boundary and replays, first with the same knobs
    (transient faults recover bit-identically), then down the
    ``degrade_ladder`` (synchronous retry), and after ``max_retries``
    surfaces ``SolverDiverged`` carrying the last healthy result;
  * a ``FaultPlan`` arms deterministic faults against exactly this
    machinery, so every recovery path above is exercised in CI.

Resume composes with elastic re-meshing: when the checkpoint's layout
matches the current setup the raw leaves are re-placed verbatim (bit
resume); when the pod/device count changed, the canonical (α, w) pair
in the checkpoint warm-starts ``init_pipeline_state`` through the PR-7
re-blocking path and the replicated leaves (PRNG key chain, gap/eps
history, adaptive latch) carry over.
"""

from __future__ import annotations

import os
import signal
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sharded import (
    ShardedResult,
    SolverSetup,
    _validate_multitask_labels,
    _validate_solver_inputs,
    build_pipeline,
    device_put_state,
    finalize_state,
    init_pipeline_state,
    pipeline_state_keys,
    prepare_solver,
)
from repro.dist.mesh import degrade_ladder
from repro.resilience.faults import FaultPlan, corrupt_payload
from repro.resilience.state import (
    SolverDiverged,
    drain_state,
    load_solver_state,
)
from repro.train.checkpoint import (
    gc_checkpoints,
    latest_step,
    save_checkpoint,
)


class ResilientResult(NamedTuple):
    """A finished resilient solve: the ordinary ``ShardedResult`` plus
    the recovery ledger the benchmarks and tests read."""

    result: ShardedResult
    health: int            # final carried watchdog code (0 = healthy)
    attempts: tuple        # per-segment attempt counts (1 = clean)
    rollbacks: int         # total tripped-segment rollbacks
    rung: int              # final degradation rung (sticky)
    epochs_lost: int       # epochs recomputed across all rollbacks
    resumed_from: Optional[int]  # checkpoint step resumed from


# state leaves that are layout-independent (replicated on every mesh):
# carried verbatim through an elastic restore so the PRNG chain, the
# recorded history and the adaptive/watchdog latches survive a re-mesh.
_REPLICATED_KEYS = ("key", "gaps", "epsb", "actb", "delayb", "slot",
                    "epoch", "delay", "gapprev", "rpok", "health",
                    "gph", "eph", "frac", "nrun", "rp")


def _target_keys(setup: SolverSetup, knobs: dict, watchdog: bool):
    """The ``SolverState`` key set a segment built with ``knobs``
    (a ``degrade_ladder`` step) carries — mirrors the builders' own
    mode resolution.  Note disabling overlap can *add* a key: the 2-D
    delayed path without overlap runs the dyn round scan and carries
    ``dwo``."""
    tn = setup.tuning
    shrink_on = tn.shrink_every > 0
    ov = bool(knobs["overlap"]) and knobs["delay_rounds"] >= 1
    dyn = (shrink_on or tn.adaptive) and not ov and not setup.pod_on
    pod_fifo = (knobs["pod_delay_rounds"]
                if (setup.pod_on and knobs["pod_delay_rounds"] > 0) else 0)
    return pipeline_state_keys(dyn=dyn, shrink_on=shrink_on,
                               adaptive=tn.adaptive, pod_fifo=pod_fifo,
                               watchdog=watchdog)


def _restore(setup: SolverSetup, ckpt_dir: str, step: int, total: int,
             *, watchdog: bool):
    """(state, epoch, rung) out of checkpoint ``step``.  Layout match →
    bit resume (raw leaves re-placed verbatim); layout change → elastic
    warm-start from the canonical (α, w) through ``_init_alpha_w``'s
    re-blocking, replicated leaves carried over."""
    raw = load_solver_state(ckpt_dir, step)
    rung = int(raw.get("meta_rung", 0))
    knobs = degrade_ladder(rung, delay_rounds=setup.delay_rounds,
                           pod_delay_rounds=setup.pod_delay_rounds,
                           overlap=setup.tuning.overlap)
    expected = set(_target_keys(setup, knobs, watchdog))
    state_raw = {k: v for k, v in raw.items()
                 if not k.startswith("meta_") and not k.endswith("_canon")}
    meta_ok = all(
        int(raw.get(f"meta_{name}", dflt)) == val
        for name, val, dflt in (
            ("pods", setup.pods, -1), ("pdata", setup.p, -1),
            ("mmodel", setup.m, -1),
            ("block_size", setup.block_size, -1),
            ("total_epochs", total, -1), ("seed", setup.seed, -1),
            # pre-task-axis checkpoints carry no n_tasks meta: default 0
            # keeps their binary bit-resume intact
            ("n_tasks", setup.n_tasks, 0)))
    a_shape = ((setup.n_tasks, setup.n_pad) if setup.n_tasks
               else (setup.n_pad,))
    w_shape = ((setup.n_tasks, setup.w_len) if setup.n_tasks
               else (setup.w_len,))
    if (meta_ok and set(state_raw) == expected
            and state_raw["alpha"].shape == a_shape
            and state_raw["w"].shape == w_shape):
        st = device_put_state(
            setup, {k: jnp.asarray(v) for k, v in state_raw.items()})
        return st, step, rung
    # elastic: the mesh (or schedule) changed — re-block the canonical
    # iterates onto the new layout; fresh dw/pbuf means any in-flight
    # aggregate the checkpoint had was already flushed into w_canon
    st = init_pipeline_state(
        setup, total_epochs=total, watchdog=watchdog,
        alpha0=raw["alpha_canon"], w0=raw["w_canon"],
        delay_rounds=knobs["delay_rounds"],
        pod_delay_rounds=knobs["pod_delay_rounds"],
        overlap_on=knobs["overlap"])
    upd = {}
    for k in _REPLICATED_KEYS:
        if (k in st and k in state_raw
                and tuple(np.shape(state_raw[k])) == tuple(st[k].shape)):
            upd[k] = jnp.asarray(state_raw[k])
    upd["epoch"] = jnp.int32(step)
    st.update(device_put_state(setup, upd))
    return st, step, rung


def solve_segmented(
    X_host,
    loss,
    *,
    epochs: int = 10,
    checkpoint_every: int | None = None,
    y=None,
    ckpt_dir: str | None = None,
    resume: bool = False,
    keep: int = 3,
    watchdog: bool = True,
    watchdog_blowup: float = 4.0,
    watchdog_floor: float = 1e-3,
    max_retries: int = 3,
    fault_plan: FaultPlan | None = None,
    alpha0=None,
    w0=None,
    mesh=None,
    mesh_axes: tuple = ("data",),
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
) -> ResilientResult:
    """Fault-tolerant ``sharded_passcode_solve``: same solver, same
    knobs, dispatched in ``checkpoint_every``-epoch segments with
    checkpointing, watchdog-driven rollback and the degradation ladder
    (module docstring).  ``checkpoint_every=None`` runs one segment
    (still watchdogged).  ``resume=True`` continues from the newest
    checkpoint in ``ckpt_dir`` when one exists — bit-identically on the
    same mesh, elastically across a changed one.  ``fault_plan`` arms
    the deterministic chaos harness (``repro.resilience.faults``)."""
    if not record:
        watchdog = False  # the watchdog keys on the record schedule
    y_host = None if y is None else np.asarray(jax.device_get(y))
    if y_host is not None and y_host.ndim == 2:
        # multi-task (K, n) one-vs-rest labels: validated, not folded —
        # the segmented pipeline threads them to the engines per segment
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
    total = int(epochs)
    seg = int(checkpoint_every) if checkpoint_every else total
    if seg < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {seg}")
    plan = fault_plan if fault_plan is not None else FaultPlan()

    resumed_from = None
    rung = 0
    e = 0
    st = None
    if resume:
        if ckpt_dir is None:
            raise ValueError("resume=True requires ckpt_dir")
        step = latest_step(ckpt_dir)
        if step is not None:
            st, e, rung = _restore(setup, ckpt_dir, step, total,
                                   watchdog=watchdog)
            resumed_from = step
    if st is None:
        st = init_pipeline_state(setup, total_epochs=total,
                                 watchdog=watchdog, alpha0=alpha0, w0=w0)

    dev_armed = True
    pay_armed = plan.corrupt_payload_segment >= 0
    pipes = {}
    attempts_log = []
    rollbacks = 0
    epochs_lost = 0

    while e < total:
        seg_len = min(seg, total - e)
        seg_idx = e // seg
        snapshot = st  # last healthy boundary (rollback target)
        attempt = 0
        while True:
            # attempt 0 and 1 keep the current rung (the transient-
            # fault same-knob replay); from attempt 2 on, drop to the
            # synchronous rung
            eff_rung = rung if attempt < 2 else 1
            knobs = degrade_ladder(
                eff_rung, delay_rounds=setup.delay_rounds,
                pod_delay_rounds=setup.pod_delay_rounds,
                overlap=setup.tuning.overlap)
            dev_fault = None
            if dev_armed:
                dev_fault = plan.device_fault(
                    delay_rounds=knobs["delay_rounds"],
                    pod_delay_rounds=knobs["pod_delay_rounds"])
            if dev_fault is not None:
                # only compile in the epochs this segment can reach
                dev_fault = tuple(v if e <= v < e + seg_len else -1
                                  for v in dev_fault)
                if all(v < 0 for v in dev_fault):
                    dev_fault = None
            X_use = setup.X
            if pay_armed and seg_idx == plan.corrupt_payload_segment:
                X_use = corrupt_payload(setup, frac=plan.corrupt_frac,
                                        seed=plan.seed)
            cache_key = (seg_len, knobs["delay_rounds"],
                         knobs["pod_delay_rounds"],
                         bool(knobs["overlap"]), dev_fault)
            fn = pipes.get(cache_key)
            if fn is None:
                fn = build_pipeline(
                    setup, epochs=seg_len, total_epochs=total,
                    segmented=True, watchdog=watchdog,
                    watchdog_blowup=watchdog_blowup,
                    watchdog_floor=watchdog_floor, fault=dev_fault,
                    delay_rounds=knobs["delay_rounds"],
                    pod_delay_rounds=knobs["pod_delay_rounds"],
                    overlap_on=knobs["overlap"])
                pipes[cache_key] = fn
            st_in = (drain_state(st, _target_keys(setup, knobs, watchdog))
                     if eff_rung > 0 else st)
            st_out = fn(X_use, setup.sq_norms, st_in, setup.Y)
            # multi-task: any tripped class trips the segment (health is
            # a (K,) vector there, a scalar on the binary path)
            health = (int(np.max(jax.device_get(st_out["health"])))
                      if watchdog else 0)
            if health == 0:
                st = st_out
                break
            # tripped: roll back to the healthy boundary and retry
            rollbacks += 1
            epochs_lost += seg_len
            attempt += 1
            st = snapshot
            if not plan.persistent:
                dev_armed = False
                pay_armed = False
            if attempt > max_retries:
                raise SolverDiverged(
                    f"segment {seg_idx} (epochs {e}..{e + seg_len}) "
                    f"still unhealthy (code {health}) after {attempt} "
                    "attempts incl. synchronous retries",
                    epoch=e,
                    history=tuple(attempts_log) + (attempt,),
                    result=finalize_state(setup, snapshot, epochs=e))
        if eff_rung == 1:
            rung = 1  # sticky: never climb back up
        attempts_log.append(attempt + 1)
        e += seg_len
        if plan.sigkill_segment == seg_idx and resumed_from is None:
            # chaos harness: die after computing the segment but BEFORE
            # checkpointing it — the resumed process (which skips this
            # arm) replays the lost segment from the previous boundary
            os.kill(os.getpid(), signal.SIGKILL)
        if ckpt_dir is not None:
            canon = finalize_state(setup, st, epochs=e)
            flat = dict(st)
            flat["alpha_canon"] = canon.alpha
            flat["w_canon"] = canon.w_hat
            flat["meta_pods"] = np.int64(setup.pods)
            flat["meta_pdata"] = np.int64(setup.p)
            flat["meta_mmodel"] = np.int64(setup.m)
            flat["meta_block_size"] = np.int64(setup.block_size)
            flat["meta_total_epochs"] = np.int64(total)
            flat["meta_seed"] = np.int64(setup.seed)
            flat["meta_n_tasks"] = np.int64(setup.n_tasks)
            flat["meta_epoch"] = np.int64(e)
            flat["meta_rung"] = np.int64(rung)
            save_checkpoint(ckpt_dir, e, flat)
            gc_checkpoints(ckpt_dir, keep=keep)

    final = finalize_state(setup, st, epochs=total)
    health_final = (int(np.max(jax.device_get(st["health"])))
                    if watchdog else 0)
    return ResilientResult(result=final, health=health_final,
                           attempts=tuple(attempts_log),
                           rollbacks=rollbacks, rung=rung,
                           epochs_lost=epochs_lost,
                           resumed_from=resumed_from)
