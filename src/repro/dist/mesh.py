"""Mesh construction and data-parallel axis helpers.

Functions (not module-level constants) so importing this module never
touches jax device state — callers control when devices are initialized
(the dry-run sets ``xla_force_host_platform_device_count=512`` first).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    jax ≥ 0.7 builds ``Explicit`` axes by default, and under them the
    solver's un-padding gathers/scatters of mesh-placed arrays cannot
    resolve an output sharding.  Every mesh in this repo is built here
    (or normalised by ``auto_mesh``) so the compiler propagates
    shardings the way the solver was written for."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def auto_mesh(mesh):
    """The same devices and axis names as ``mesh``, with ``Auto`` axis
    types — applied at the solver's mouth so a caller-built
    ``jax.make_mesh(...)`` (Explicit by default) still works."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis
    composes with ``data`` for the DP gradient reduction and carries the
    cross-pod (DCN-ish) collectives that the dry-run must prove shard."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def solver_mesh(axis: str = "data", n_devices: int | None = None):
    """1-D mesh for the dual-coordinate solvers: every local device along
    one named axis.  ``axis="data"`` is the paper's thread→device mapping
    (rows / dual coordinates sharded); ``axis="model"`` is the
    feature-sharded deployment (w sharded, psum per dot product)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n,), (axis,))


def solver_mesh_2d(data: int | None = None, model: int = 1,
                   n_devices: int | None = None):
    """2-D ``(data, model)`` mesh for the feature-sharded solver: rows /
    dual coordinates block-parallelize along ``data`` (the paper's
    thread→device mapping), w and the feature dimension shard along
    ``model`` (the per-coordinate dot product psums over it — the mesh
    analogue of the paper's atomic adds into shared w, DESIGN.md §10).
    ``data`` defaults to all remaining devices."""
    n = n_devices or len(jax.devices())
    if data is None:
        data = max(n // model, 1)
    return make_mesh((data, model), ("data", "model"))


def solver_mesh_3d(pod: int = 2, data: int | None = None, model: int = 1,
                   n_devices: int | None = None):
    """3-D ``(pod, data, model)`` mesh for the double-async pod solver
    (DESIGN.md §13): each pod runs the existing pipelined 1D/2D PASSCoDe
    solve on its local row shard — rows/duals block-parallelize along
    ``data``, features optionally along ``model``, both *pod-local*
    collectives — while the ``pod`` axis carries only the CoCoA-style
    Δw-average merge, a per-outer-round psum that the
    ``pod_delay_rounds`` staleness knob may keep in flight.  ``data``
    defaults to all remaining devices."""
    n = n_devices or len(jax.devices())
    if data is None:
        data = max(n // (pod * model), 1)
    return make_mesh((pod, data, model), ("pod", "data", "model"))


def solver_mesh_tasks(task: int = 2, data: int | None = None,
                      model: int = 1, n_devices: int | None = None):
    """Mesh with a leading ``task`` axis for the multi-task one-vs-rest
    solver (DESIGN.md §16): each of K one-vs-rest problems shares one X
    (replicated along ``task`` — no spec names the axis for it) while
    the per-class (α, w) stacks shard their leading (K,) axis over it.
    Use when K is large enough that a replicated (K, n)+(K, d) state
    stack stops fitting per-device; for small K the plain meshes with
    the vmapped task axis are strictly cheaper (no extra collectives).
    ``data`` defaults to all remaining devices; ``model > 1`` appends
    the feature-sharding axis like ``solver_mesh_2d``."""
    n = n_devices or len(jax.devices())
    if data is None:
        data = max(n // (task * model), 1)
    if model > 1:
        return make_mesh((task, data, model), ("task", "data", "model"))
    return make_mesh((task, data), ("task", "data"))


def task_axis_policy(n_tasks: int, *, mesh) -> int:
    """Admission rule for the multi-task (one-vs-rest) task axis
    (DESIGN.md §16) — which knob combinations admit a leading (K,) task
    axis is *distribution* policy, so it lives here next to
    ``solver_mesh_tasks``.

    The vmapped task axis (no ``task`` mesh axis) composes with every
    existing knob — pod merges, shrinking, adaptive delay, overlap,
    segmented resume — because each task carries its own latches and
    the shared epoch counter stays an unbatched scalar.  Restrictions:

      * a ``task`` mesh axis needs ``n_tasks`` divisible by its size
        (the per-class state stack shards evenly, no padding classes);
      * ``task`` + ``pod`` on one mesh is rejected: the cross-pod merge
        scan assumes the pod axis is the outermost parallelism and the
        per-pod row layout is task-uniform — shard K over pods instead
        by running one multi-task solve per pod.

    Returns the validated ``n_tasks``."""
    K = int(n_tasks)
    if K < 1:
        raise ValueError(f"n_tasks must be >= 1, got {n_tasks}")
    if "task" in mesh.axis_names:
        t = mesh.shape["task"]
        if K % t:
            raise ValueError(
                f"n_tasks={K} does not divide over the task mesh axis "
                f"of size {t} — the per-class state stack must shard "
                "evenly (no padding classes)")
        if "pod" in mesh.axis_names:
            raise ValueError(
                "a 'task' mesh axis does not compose with a 'pod' axis "
                "— run one multi-task solve per pod instead")
    return K


def pod_merge_policy(pod_delay_rounds: int, *, n_pods: int,
                     record: bool = True,
                     shrink_every: int = 0, adaptive: bool = False,
                     overlap: bool | str = "auto") -> int:
    """Admission/staleness rule for the cross-pod primal merge
    (DESIGN.md §13) — the pod-level analogue of ``pipeline_overlap`` +
    ``resolve_self_tuning``: whether (and how stale) the delayed
    cross-pod allreduce may run is *distribution* policy, so it lives
    here next to ``solver_mesh_3d``.

    ``pod_delay_rounds = k`` lets the merge aggregate issued at outer
    round t arrive at round t+k (a FIFO of k in-flight scaled psums —
    modelling a DCN allreduce that takes k outer rounds), so every pod
    reads a primal that lags the true w(α) by at most k merge rounds:
    bounded staleness, PASSCoDe Assumption 1 lifted to the pod level.
    ``k = 0`` is the synchronous CoCoA outer round exactly.

    Returns the validated ``pod_delay_rounds``.  Raises on
    combinations the pod merge scan does not (yet) compose with:

      * ``shrink_every >= 1`` — the active mask lives in the dyn round
        scan, which the pod path's static inner rounds do not run;
      * ``overlap=True`` — the in-flight (base, Gram) psum is only
        valid under the plain epoch schedule, not the merge-rescaled
        one ("auto" resolves off, like everywhere else);
      * ``adaptive`` without ``record`` — the pod-level anneal latch
        (``adaptive_delay_policy`` on the recorded gap trend) needs the
        gap buffer as its input signal.
    """
    k = int(pod_delay_rounds)
    if k < 0:
        raise ValueError(
            f"pod_delay_rounds must be >= 0, got {pod_delay_rounds}")
    if int(n_pods) < 1:
        raise ValueError(f"n_pods must be >= 1, got {n_pods}")
    if shrink_every:
        raise ValueError(
            "shrink_every is not composed with the pod merge loop — "
            "the active mask needs the dyn round scan, which the pod "
            "path's static inner rounds do not run")
    if overlap is True:
        raise ValueError(
            "overlap=True is not composed with the pod merge loop — "
            "the in-flight (base, Gram) psum is only valid under the "
            "plain epoch schedule, not the merge-rescaled one; leave "
            "overlap='auto'")
    if adaptive and not record:
        raise ValueError(
            "adaptive=True needs record=True — the pod-level anneal "
            "latch reads the on-device duality-gap buffer as its input "
            "signal")
    return k


def data_axes(mesh) -> tuple:
    """Axes that form the data-parallel dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


# ------------------------------------------------------- VMEM policy ----
# Whether a solver shard fits on-chip is *distribution* policy (it decides
# between the fused Pallas round and the pure-jnp fallback in
# ``repro.core.sharded``), so it lives here next to ``solver_mesh`` rather
# than in the kernel package.  DESIGN.md §6.

VMEM_BYTES = 16 * 2**20  # per-TensorCore VMEM (v4/v5-class parts)
# per-TensorCore scalar memory: a described-v5e compile of the ragged
# ELL kernel refuses a row buffer past 1 MiB of SMEM in all
SMEM_BYTES = 2**20


def lane_pad(d: int, lanes: int = 128) -> int:
    """Round ``d`` up to the TPU lane tile (128 f32 lanes) — the padding
    every fused kernel path applies to its minor dimension."""
    return ((d + lanes - 1) // lanes) * lanes


# back-compat alias (pre-PR-5 modules imported the underscored name)
_lane_pad = lane_pad


def dcd_kernel_vmem_bytes(n_loc: int, d: int, *, itemsize: int = 4,
                          n_tasks: int = 1) -> int:
    """Resident working set of the fused indexed-block DCD round: the
    whole (n_loc, d̃) local shard plus w in/out (2·d̃), α in/out + q +
    the active-set mask (4·n_loc f32 — the mask operand is always bound,
    all-ones when shrinking is off) and the int32 index block (n_loc
    upper bound).  ``n_tasks > 1`` (the multi-task axis, DESIGN.md §16)
    multiplies the per-task operands — w in/out, α in/out, and the
    mask/label word — while X, q, and the index block stay shared across
    the K one-vs-rest problems; ``n_tasks=1`` is today's binary formula
    exactly."""
    dp = lane_pad(d)
    K = max(int(n_tasks), 1)
    return (itemsize * (n_loc * dp + n_loc + K * (2 * dp + 3 * n_loc))
            + 4 * n_loc)


def dcd_kernel_fits(n_loc: int, d: int, *, vmem_bytes: int = VMEM_BYTES,
                    headroom: float = 0.9, n_tasks: int = 1) -> bool:
    """True when a device's row shard can stay VMEM-resident for the fused
    kernel; otherwise ``sharded_passcode_solve(use_kernel="auto")`` keeps
    the pure-jnp block update."""
    return dcd_kernel_vmem_bytes(n_loc, d, n_tasks=n_tasks) <= (
        headroom * vmem_bytes)


def dcd_ell_kernel_vmem_bytes(d: int, *, block_size: int = 64,
                              itemsize: int = 4) -> int:
    """Resident working set of the fused *ELL* block kernel (DESIGN.md
    §9), which streams the row shard from HBM: the padded primal in and
    out (2·d₁ with d₁ = lane_pad(d+1) for the dummy slot) and the block's
    per-step operands — α seed, ‖x‖², active mask and label in, α out —
    five (B, 1) columns of one lane tile each (5·B·128 words).  The
    row's ids and values sit in SMEM, double-buffered, and nothing grows
    with n_loc or k_max: admission depends on d alone.  A multi-task
    solve runs the kernel over a task grid, one head's primal at a time,
    in the same working set.

    Tied to the compiler: for a described v5e, under Mosaic's default
    scoped VMEM limit (16 MiB, = ``VMEM_BYTES``), the kernel compiles up
    to d ≈ 2.096M, where 2·d₁ words are 16.77 MB, alone and vmapped over
    8 heads; ``tests/test_tpu_compile.py`` compiles it at this policy's
    frontier."""
    words = 2 * lane_pad(d + 1) + 5 * block_size * 128
    return itemsize * words


def dcd_ell_kernel_fits(d: int, *, vmem_bytes: int = VMEM_BYTES,
                        headroom: float = 0.9,
                        block_size: int = 64) -> bool:
    """True when the fused sparse kernel's resident primal fits VMEM —
    rcv1 (d 47,236: 0.5 MB) and news20 (d 1,355,191: 11 MB) do, webspam
    (d 16.6M) does not; otherwise
    ``sharded_passcode_solve(use_kernel="auto")`` keeps the unfused jnp
    ELL block update."""
    return dcd_ell_kernel_vmem_bytes(d, block_size=block_size) <= (
        headroom * vmem_bytes)


def dcd_ragged_kernel_smem_bytes(chunk_tiles: int, *, block_size: int = 64,
                                 itemsize: int = 4) -> int:
    """SMEM of the fused kernel for packed ragged rows: two slots of
    ``chunk_tiles`` lane tiles of ids and of values (the row buffer,
    fixed whatever the rows' lengths, since a longer row streams in
    chunks) and the block's per-step first tile, offset, width and
    source step (4·B words)."""
    return itemsize * (2 * 2 * chunk_tiles * 128 + 4 * block_size)


def dcd_ragged_kernel_fits(d: int, chunk_tiles: int, *,
                           block_size: int = 64,
                           vmem_bytes: int = VMEM_BYTES,
                           smem_bytes: int = SMEM_BYTES,
                           headroom: float = 0.9) -> bool:
    """Admission of the ragged kernel: the ELL kernel's VMEM policy (the
    primal and the per-step columns; the rows stream from HBM, so
    neither n_loc nor any row length enters) and its fixed SMEM row
    buffer."""
    return (dcd_ell_kernel_fits(d, vmem_bytes=vmem_bytes,
                                headroom=headroom, block_size=block_size)
            and dcd_ragged_kernel_smem_bytes(
                chunk_tiles, block_size=block_size)
            <= headroom * smem_bytes)

def dcd_feature_kernel_vmem_bytes(n_loc: int, k_loc: int, d_loc: int, *,
                                  block_size: int = 256,
                                  itemsize: int = 4,
                                  n_tasks: int = 1) -> int:
    """Resident working set of the fused *2D feature-sharded* block round
    (DESIGN.md §10): the (n_loc, k̃_loc) local-column-id and value slices
    (2·n_loc·k̃_loc words, k̃_loc lane-padded), the device's own primal
    *shard* in/out (2·d₁_loc with d₁_loc = lane_pad(d_loc + 1) for the
    per-shard dummy slot — this is the d/m term that makes huge d
    feasible), α in/out + q + the active-set mask (4·n_loc f32), the
    int32 index block, and the per-block Gram/base exchange buffers
    (B² + O(B) f32).

    The only d-dependent term is 2·d₁_loc ≈ 2·d/m: at m = 16 this admits
    webspam/kddb-scale d ≈ 16.6M, where the dense policy's n_loc·d̃ and
    the 1D ELL policy's 2·lane_pad(d+1) primal both exceed VMEM.

    ``n_tasks > 1`` multiplies the per-task operands — primal-shard
    in/out, α in/out, mask/label word, and the per-block Gram/base
    exchange buffers (each task's split round carries its own) — while
    the ELL slice, q, and the index block stay shared."""
    kp = lane_pad(k_loc)
    d1 = lane_pad(d_loc + 1)
    b = block_size
    K = max(int(n_tasks), 1)
    return (itemsize * (2 * n_loc * kp + n_loc
                        + K * (2 * d1 + 3 * n_loc + b * b + 3 * b))
            + 4 * n_loc + 4 * b)


def dcd_feature_kernel_fits(n_loc: int, k_loc: int, d_loc: int, *,
                            block_size: int = 256,
                            vmem_bytes: int = VMEM_BYTES,
                            headroom: float = 0.9,
                            n_tasks: int = 1) -> bool:
    """True when a device's (row-block × feature-shard) slice can stay
    VMEM-resident for the fused 2D kernel; otherwise
    ``sharded_passcode_solve(use_kernel="auto")`` keeps the unfused jnp
    feature-sharded block update."""
    return dcd_feature_kernel_vmem_bytes(
        n_loc, k_loc, d_loc, block_size=block_size, n_tasks=n_tasks
    ) <= headroom * vmem_bytes


def pipeline_overlap(overlap, *, two_d: bool, fused: bool,
                     delay_rounds: int) -> bool:
    """Resolve the solver's ``overlap`` knob ∈ {False, True, "auto"} —
    whether the 2-D block round double-buffers its ``model``-axis
    (base, Gram) psum behind the next block's gram kernel (DESIGN.md
    §11).  Like the VMEM admission rules above, when a round pipelines
    is *distribution* policy.

    The overlapped round needs (a) the fused 2-D engine, whose split
    gram/update phases expose an aggregate that can stay in flight — the
    unfused engine psums per update and the 1-D meshes have no
    ``model``-axis psum at all — and (b) ``delay_rounds ≥ 1``, the
    staleness bookkeeping (carried in-flight Δw) the overlapped schedule
    piggybacks on.  ``"auto"`` enables it exactly there; forcing ``True``
    elsewhere raises rather than silently changing semantics."""
    if overlap == "auto":
        return bool(two_d and fused and delay_rounds >= 1)
    overlap = bool(overlap)
    if not overlap:
        return False
    if not two_d:
        raise ValueError(
            "overlap=True needs a 2-D ('data', 'model') mesh — a 1-D "
            "mesh has no model-axis psum to double-buffer")
    if not fused:
        raise ValueError(
            "overlap=True needs the fused kernel path (use_kernel=True "
            "or an admitting 'auto') — only the split gram/update "
            "phases expose a (base, Gram) aggregate to keep in flight")
    if delay_rounds < 1:
        raise ValueError(
            "overlap=True needs delay_rounds >= 1 — the overlapped "
            "round carries its aggregates with the delayed-round "
            "bookkeeping")
    return True


def adaptive_delay_policy(gap_prev, gap_new, *, improve_ratio: float = 0.95):
    """Gap-trend controller for the effective asynchrony (DESIGN.md §12).

    Maps two consecutive recorded duality gaps to the next delay flag:
    1 (delayed psum — one round of staleness, maximal overlap) while the
    gap is still improving by at least ``1 − improve_ratio`` per record
    interval, 0 (synchronous rounds) once it stalls or regresses.  This
    is the paper's staleness-vs-convergence tradeoff run closed-loop:
    inside the Liu–Wright admissible region asynchrony is free, so take
    the overlap; when progress stalls the gap trend is the observable
    symptom, so fall back to the synchronous schedule instead of burning
    epochs on stale updates.

    jnp-traceable (the solver evaluates it inside the epoch scan on the
    psummed — hence device-uniform — gap, so the flag it returns is
    uniform too and may gate collectives).  Monotone in the trend:
    a smaller ``gap_new`` never decreases the returned asynchrony.
    Returns int32 0/1.

    The pipelined solver applies this through a one-way latch (it only
    ever lowers the carried flag): re-raising oscillates, because a
    synchronous epoch's fast progress reads as "async affordable" and
    the following stale epoch's slow progress reads as "back off",
    re-paying the staleness tax each flip.
    """
    return (gap_new <= improve_ratio * gap_prev).astype(jnp.int32)


def watchdog_trip(gap_prev, gap_new, eps_prev, eps_new, n_bad, *,
                  blowup: float = 4.0, floor: float = 1e-3):
    """On-device divergence watchdog for the pipelined solve
    (DESIGN.md §14) — the health-code companion of
    ``adaptive_delay_policy``: where the adaptive controller reads the
    recorded gap trend to *tune* asynchrony, this reads the same trend
    (plus the backward error ε = ‖w(α) − ŵ‖ of Table 2 and a NaN/Inf
    census of the carried α/w) to decide whether the solve is still
    healthy at all.

    Inputs are the previous *healthy* record's (gap, eps) — seeded with
    +inf so the first record only establishes the baseline — the fresh
    record, and ``n_bad``, the psummed count of non-finite entries in
    (α, ŵ).  Returns an int32 health code, device-uniform because every
    input is:

      0  healthy — the record becomes the next baseline;
      1  divergence trend — gap or eps blew past ``blowup`` × its last
         healthy value + ``floor`` (the absolute floor keeps float-noise
         jitter around a converged eps ~1e-7 from tripping; a dropped or
         duplicated pod merge shows up as an eps jump of O(‖Δw‖), orders
         above it);
      2  non-finite — anything NaN/Inf in α, ŵ, the gap or eps (a
         poisoned psum lands here within one record interval).

    jnp-traceable; the epoch scan latches ``max`` of the codes so a trip
    is sticky for the rest of the segment and the rollback harness
    (``repro.resilience``) reads one scalar after the dispatch."""
    nonfin = ((n_bad > 0) | ~jnp.isfinite(gap_new)
              | ~jnp.isfinite(eps_new))
    div = ((gap_new > blowup * gap_prev + floor)
           | (eps_new > blowup * eps_prev + floor))
    return jnp.where(nonfin, 2, jnp.where(div, 1, 0)).astype(jnp.int32)


def degrade_ladder(rung: int, *, delay_rounds: int,
                   pod_delay_rounds: int, overlap) -> dict:
    """Graceful-degradation ladder for the rollback harness
    (DESIGN.md §14) — which asynchrony knobs a retry of a tripped
    segment may keep.  Like ``pod_merge_policy``/``pipeline_overlap``,
    *how much staleness a recovery is allowed* is distribution policy,
    so it lives here; ``repro.resilience.solve_segmented`` consumes it.

    Rung 0 replays the segment with the original knobs — the
    transient-fault assumption (a poisoned psum, a corrupted payload
    that re-materialization heals): replay from the healthy snapshot is
    then *bit-identical* to the fault-free solve.  Rung 1 is the
    persistent-fault response, applied when a same-knob retry trips
    again: latch ``delay_rounds → 0``, drain the pod FIFO
    (``pod_delay_rounds → 0``) and disable overlap — every source of
    staleness the Liu–Wright bound charges is removed, trading speed
    for the synchronous schedule's stability, exactly the one-way
    direction ``adaptive_delay_policy`` anneals in.  Rungs are sticky
    (the harness never climbs back up) and bounded by its retry budget,
    after which ``SolverDiverged`` surfaces instead of silent garbage.
    """
    if rung <= 0:
        return {"rung": 0, "delay_rounds": int(delay_rounds),
                "pod_delay_rounds": int(pod_delay_rounds),
                "overlap": overlap}
    return {"rung": 1, "delay_rounds": 0, "pod_delay_rounds": 0,
            "overlap": False}


class SelfTuning(NamedTuple):
    """Resolved self-tuning configuration of one solve (see
    ``resolve_self_tuning``)."""

    shrink_every: int
    repack: bool
    adaptive: bool
    overlap: bool


def resolve_self_tuning(shrink_every, repack, adaptive, *, overlap_knob,
                        overlap_on: bool, record: bool) -> SelfTuning:
    """Resolve/validate the solver's self-tuning knobs (DESIGN.md §12).

    ``shrink_every`` ∈ {0 = off, k ≥ 1}: recompute the active mask every
    k epochs.  ``repack`` ∈ {False, True, "auto"}: draw repacked epochs
    over the compacted active set so they take fewer block rounds.
    ``adaptive`` toggles the gap-trend delay controller.  Mask, repack
    ids and the delay flag all live in the epoch scan's carry, and the
    controller needs the recorded gap as its input signal.

    Interactions with the 2-D overlapped schedule: the overlapped round
    keeps a (base, Gram) psum in flight that is only valid for the block
    sequence it was issued against, so a repacked draw (sequence changes
    with the mask) or a controller dropping to synchronous mid-solve
    would invalidate it.  ``overlap="auto"`` therefore resolves *off*
    when shrinking or adaptive is requested (repack's shorter epochs are
    the measured win; overlap only hides collective latency), while an
    explicit ``overlap=True`` keeps plain masked shrinking but rejects
    repack/adaptive rather than silently changing semantics.
    """
    every = int(shrink_every or 0)
    if every < 0:
        raise ValueError(f"shrink_every must be >= 0, got {shrink_every}")
    adaptive = bool(adaptive)
    if adaptive and not record:
        raise ValueError(
            "adaptive=True needs record=True — the gap-trend controller "
            "reads the on-device duality-gap buffer as its input signal")
    if repack not in (False, True, "auto"):
        raise ValueError(f"repack must be False/True/'auto', got {repack!r}")
    if repack is True and not every:
        raise ValueError("repack=True needs shrink_every >= 1 — there is "
                         "no active set to compact without shrinking")
    if overlap_on and (every or adaptive):
        if overlap_knob == "auto":
            overlap_on = False
        elif repack is True or adaptive:
            raise ValueError(
                "overlap=True is incompatible with repack/adaptive — the "
                "in-flight (base, Gram) psum is only valid for a fixed "
                "block sequence under a fixed delay schedule")
    if repack == "auto":
        repack = bool(every) and not overlap_on
    if repack and overlap_on:
        raise ValueError(
            "repack=True is incompatible with the overlapped schedule — "
            "the repacked draw changes the block sequence the in-flight "
            "gram was issued against")
    return SelfTuning(every, bool(repack), adaptive, overlap_on)


def dcd_block_rows(d: int, *, vmem_bytes: int = VMEM_BYTES,
                   headroom: float = 0.9, max_rows: int = 512) -> int:
    """Largest power-of-two row tile for the *contiguous* epoch kernel
    whose (B, d̃) tile + w + per-row vectors fit the VMEM budget."""
    dp = lane_pad(d)
    b = max_rows
    while b > 8 and 4 * (b * dp + 2 * dp + 3 * b) > headroom * vmem_bytes:
        b //= 2
    return b


# --- serving admission / degradation policy (DESIGN.md §15) ----------
#
# Like the VMEM admission predicates above, *what load the serving
# engine may admit and how it backs off under pressure* is distribution
# policy: it decides how much work reaches the mesh per dispatch.  The
# engine in ``repro.serve`` only consumes these rules.


def serve_admission_policy(*, queue_depth: int, max_batch: int,
                           deadline_s: float, swap_grace_s: float) -> dict:
    """Validate and normalise the serving admission knobs
    (DESIGN.md §15).

    ``queue_depth`` bounds the request queue — beyond it, offers are
    refused and the caller sheds with a backpressure outcome instead of
    growing an unbounded backlog.  ``max_batch`` is the scoring
    dispatch's compiled batch shape (the degrade ladder only lowers the
    *live* count, never the shape, so overload can't trigger a
    recompile storm).  ``deadline_s`` is the default per-request
    deadline; ``swap_grace_s`` bounds how long a hot-swap publish waits
    for pinned readers to drain before returning with stragglers still
    in flight (they finish on the old snapshot — drained late beats
    dropped)."""
    depth, batch = int(queue_depth), int(max_batch)
    if depth < 1:
        raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
    if batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if not (float(deadline_s) > 0.0):
        raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
    if float(swap_grace_s) < 0.0:
        raise ValueError(
            f"swap_grace_s must be >= 0, got {swap_grace_s}")
    return {"queue_depth": depth, "max_batch": batch,
            "deadline_s": float(deadline_s),
            "swap_grace_s": float(swap_grace_s)}


def serve_rung(occupancy: float, prev_rung: int = 0, *,
               up: tuple = (0.5, 0.85),
               down: tuple = (0.2, 0.6)) -> int:
    """Occupancy-driven rung selector for ``serve_degrade_ladder``
    (DESIGN.md §15), with hysteresis so a queue hovering at a threshold
    doesn't flap the ladder every step.

    ``occupancy`` is queue fill ∈ [0, 1].  Climb to rung r+1 while
    occupancy ≥ ``up[r]``; descend to rung r−1 only once occupancy has
    fallen below ``down[r−1]`` (< the matching ``up``, giving the dead
    band).  Unlike the solver's recovery ladder this one is *not*
    sticky — overload is a load condition, not a fault, and the engine
    should return to full service when the flood passes."""
    occ = float(occupancy)
    r = int(prev_rung)
    if not (0 <= r <= len(up)):
        raise ValueError(f"prev_rung out of range: {prev_rung}")
    while r < len(up) and occ >= up[r]:
        r += 1
    while r > 0 and occ < down[r - 1]:
        r -= 1
    return r


def serve_degrade_ladder(rung: int, *, max_batch: int) -> dict:
    """Overload-degradation ladder for the serving engine
    (DESIGN.md §15) — the serve-side mirror of the solver's
    ``degrade_ladder``: which throughput knobs each pressure rung
    keeps.

    Rung 0 is full service: score at the full compiled ``max_batch``
    and let incremental training run.  Rung 1 shrinks the *live* batch
    to ``max_batch // 4`` (the compiled shape is unchanged) so each
    dispatch returns sooner and deadline-expired requests are shed at a
    finer cadence — bounding tail latency at the cost of peak
    throughput.  Rung 2 additionally pauses incremental training
    (``train=False``): the engine answers from the last healthy
    snapshot only, spending every cycle draining the queue — the
    stale-model-only mode the paper's staleness tolerance makes safe.
    Rungs above 2 clamp to 2."""
    r = max(0, min(int(rung), 2))
    if int(max_batch) < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    live = int(max_batch) if r == 0 else max(1, int(max_batch) // 4)
    return {"rung": r, "max_batch": live, "train": r < 2}


def drift_trip(err_base, err_new, *, ratio: float = 2.0,
               floor: float = 0.05):
    """Distribution-drift trigger for the warm-start re-solve
    (DESIGN.md §15) — the serve-side sibling of ``watchdog_trip``:
    where the watchdog reads the solver's own health trend, this reads
    the *model-vs-stream* trend, the misclassification rate of the
    published snapshot on freshly ingested labeled rows.

    Trips (returns 1) when the fresh error exceeds ``ratio`` × the
    error the snapshot had on the data it was trained against plus an
    absolute ``floor`` — the floor keeps small-sample noise on a
    near-perfect baseline (err_base ≈ 0) from tripping on one bad row.
    jnp-traceable and device-uniform like the watchdog."""
    return (err_new > ratio * err_base + floor).astype(jnp.int32)
