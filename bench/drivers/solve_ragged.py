"""Window driver for solve traffic on rows of heavy-tailed length:
``solve.py``'s window on a configuration drawn by ``bench/gen_ragged.py``.

Set-up: the split's compressed rows from the seed, one ``prepare_solver``
on them as a ``CsrMatrix`` with the defaults users get
(``use_kernel="auto"``, ``block_size=64``, ``gap_every=1``,
``record=True``) on a ``data`` mesh of the cell's chips, and the
compile of the one-epoch pipeline.  The program packs the rows itself;
the engine it picks is printed.

Window, trace slice and layer dispatches: as ``solve.py`` (each solve
from α = 0 to ``target_rel_gap`` of g(0), its own update order from the
seed, the crossing interpolated on log-gap).  The run record adds the
program's layout counters (``nnz``, ``slots_walked``, ``buckets``,
``chunked_rows``), the block size and the device kind, for the
per-layer readers.  Every finished solve is checked against the float64
CSR reference of ``bench/reference_ragged.py``.

Parameters (``bench/traffic/<mix>.json``): those of ``solve.py``.

The control (``bench/tools/control.py``) puts a plain dual coordinate
descent over the same CSR rows, with its state and products in
bfloat16, in the program's place (``_control``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import gen_ragged, reference_ragged
from bench.drivers.solve import (
    _compile_epoch,
    _layer_dispatches,
    _program_loss,
    crossing,
    solve_seed,
)
from bench.harness import Check, Outcome, peak_bytes, span_totals

CONTROL_EPOCHS = 6  # as bench/control_dcd.py: sound solves need 4 to 5


def run(ctx) -> Outcome:
    import jax

    from repro.core import sharded
    from repro.data.sparse import CsrMatrix
    from repro.dist.mesh import make_mesh

    cfg, mix, spans = ctx.config, ctx.traffic, ctx.spans
    chips = int(mix["data"])
    if chips != ctx.cell.chips:
        raise ValueError(f"mix asks for data={chips}, the cell for "
                         f"{ctx.cell.chips} chips")
    cap = int(mix["epoch_cap"])
    g0 = reference_ragged.zero_gap(cfg)
    target = float(mix["target_rel_gap"]) * g0
    now = time.perf_counter

    with spans("bench.setup.data"):
        train = gen_ragged.make_split(cfg, ctx.seed)
        jax.block_until_ready(train.values)
    if ctx.control == "bf16":
        return _control(ctx, train, g0, target)
    mesh = make_mesh((chips,), ("data",), devices=ctx.devices[:chips])
    t0 = now()
    with spans("bench.setup.prepare"):
        setup = sharded.prepare_solver(
            CsrMatrix(train.indices, train.values, train.indptr, train.d),
            _program_loss(cfg), mesh=mesh, use_kernel="auto",
            block_size=64, gap_every=1, record=True,
            seed=solve_seed(ctx.seed, 0))
        jax.block_until_ready((setup.X, setup.sq_norms))
    prepare_s = now() - t0
    host = (np.asarray(train.indices), np.asarray(train.values),
            train.indptr)
    del train
    with spans("bench.setup.compile"):
        epoch = _compile_epoch(sharded, setup, cap)
        st = sharded.init_pipeline_state(setup, total_epochs=cap)
        res = sharded.finalize_state(setup, st, epochs=cap)
        np.asarray(st["gaps"]), np.asarray(res.alpha), np.asarray(res.w_hat)
    setup_s = now() - ctx.t_process
    compile_s, compile_events, _ = ctx.clock.read()

    solves, capped, window_s = _window(ctx, sharded, setup, epoch, cap,
                                       g0, target)
    window_compiles = ctx.clock.read()[1] - compile_events
    mem = peak_bytes(ctx.devices[:chips])

    lay = setup.layout
    rec = {"kind": "solve", "prepare_s": prepare_s, "compile_s": compile_s,
           "window_compiles": window_compiles,
           "updates_per_device": setup.n_blocks * setup.block_size,
           "block_size": setup.block_size, "n_rows": setup.n,
           "device_kind": ctx.devices[0].device_kind,
           "solves": len(solves), "engine": sharded.engine_name(setup)}
    if lay is not None:
        rec.update(nnz=lay.nnz, slots_walked=lay.slots_walked,
                   buckets=lay.buckets, chunked_rows=lay.chunked_rows)
    if solves:
        rec["epochs_to_target"] = float(np.mean([s["epochs"]
                                                 for s in solves]))
    rec["trace"] = ctx.stop_trace()
    if ctx.trace:
        rec.update(_layer_dispatches(ctx, sharded, setup, epoch, cap))

    del setup, epoch, st, res
    checks = _check(ctx, host, solves)
    # with no finished solve, the time spent without reaching the target
    # stands in (the run is then not correct)
    e2e = {"setup_s": setup_s,
           "solve_s": (float(np.mean([s["seconds"] for s in solves]))
                       if solves else window_s)}
    setup_spans = span_totals(spans, "bench.setup")
    print(f"bench: engine {rec['engine']}; layout nnz {rec.get('nnz')} "
          f"slots {rec.get('slots_walked')} buckets {rec.get('buckets')} "
          f"chunked rows {rec.get('chunked_rows')}; {len(solves)} solves, "
          f"{capped} capped, gaps of the first "
          f"{[round(g, 3) for g in solves[0]['gaps']] if solves else []}, "
          f"{window_compiles} compiles in the window; solve seconds "
          f"{[round(s['seconds'], 4) for s in solves]}; set-up spans "
          f"{ {k: round(v, 3) for k, v in setup_spans.items()} }",
          file=ctx.log)
    return Outcome(end_to_end=e2e, rec=rec, checks=checks,
                   attempted=len(solves) + capped, failed=capped,
                   correct_extra=bool(solves), memory_peak_bytes=mem)


def _window(ctx, sharded, setup, epoch, cap: int, g0: float,
            target: float):
    """``solve.py``'s window: solves back to back until the window
    closes, each to the target; returns (finished solves, capped, the
    window's seconds)."""
    now = time.perf_counter
    spans = ctx.spans
    solves, capped = [], 0
    slice_s = float(ctx.traffic["trace_seconds"])
    with spans("bench.window"):
        t_w = now()
        t_end = t_w + ctx.seconds
        j = 0
        while not solves or now() < t_end:
            s_setup = setup._replace(seed=solve_seed(ctx.seed, j))
            j += 1
            t_s = now()
            with spans("bench.solve_init"):
                st = sharded.init_pipeline_state(s_setup, total_epochs=cap)
            times, gaps, status = [0.0], [g0], "capped"
            for e in range(cap):
                with spans("bench.epoch"):
                    st = epoch(setup.X, setup.sq_norms, st)
                if j == 1 and e == 1:
                    ctx.start_trace(slice_s, at=now() + max(
                        times[1] - slice_s / 2, 0.0))
                ctx.trace_poll(st["gaps"])
                with spans("bench.gap_read"):
                    g = float(np.asarray(st["gaps"])[e])
                t = now()
                times.append(t - t_s)
                gaps.append(g)
                if g <= target:
                    status = "done" if (t <= t_end or not solves) else "cut"
                    break
                if t > t_end and solves:
                    status = "cut"
                    break
            if status == "cut":
                break
            if status == "capped":
                capped += 1
                if not solves and now() > t_end:
                    break
                continue
            with spans("bench.finalize"):
                res = sharded.finalize_state(setup, st, epochs=cap)
                alpha, w_hat = np.asarray(res.alpha), np.asarray(res.w_hat)
            t_cross, e_cross = crossing(times, gaps, target)
            solves.append({"seconds": t_cross, "epochs": e_cross,
                           "alpha": alpha, "w_hat": w_hat,
                           "gap": gaps[-1], "gaps": gaps})
    return solves, capped, now() - t_w


def _check(ctx, host, solves) -> list:
    """Each finished solve against the float64 CSR reference; the worst
    over the solves of each number, beside its limit."""
    lim = ctx.traffic["limits"]
    if not solves:
        return []  # no answer came: not correct
    ids, vals, indptr = host
    loss = reference_ragged.loss_of(ctx.config)
    d = int(ctx.config["d"])
    worst = {"inv": 0.0, "box": 0.0, "gap": 0.0}
    for s in solves:
        got = reference_ragged.solve_checks(ids, vals, indptr, d, loss,
                                            s["alpha"], s["w_hat"],
                                            s["gap"])
        for key in worst:
            worst[key] = max(worst[key], got[key])
    return [Check(key, worst[key], float(lim[key])) for key in worst]


def _control_epoch(ids, vals, indptr, d: int, C: float):
    """``epoch(alpha, w, key) -> (alpha, w, gap)``: a plain bfloat16
    dual coordinate descent over the CSR rows (hinge), every row once in
    the order ``permutation(key)``, each row read as a window of the
    longest row's width masked to its own length; sums in float32."""
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16
    indptr = np.asarray(indptr, np.int64)
    n = indptr.size - 1
    lens = np.diff(indptr)
    width = max(-(-int(lens.max()) // 16) * 16, 16)
    starts = jnp.asarray(indptr[:-1], jnp.int32)
    lens_d = jnp.asarray(lens, jnp.int32)
    rows = jnp.asarray(np.repeat(np.arange(n), lens), jnp.int32)
    ids_d = jnp.asarray(ids, jnp.int32)
    x_d = jnp.asarray(vals, jnp.float32).astype(dt)
    ids_w = jnp.concatenate([ids_d, jnp.full((width,), d, jnp.int32)])
    x_w = jnp.concatenate([x_d, jnp.zeros((width,), dt)])
    sq = jnp.zeros((n,), jnp.float32).at[rows].add(
        jnp.square(x_d.astype(jnp.float32)))
    lane = jnp.arange(width)

    def update(t, carry):
        alpha, w, perm = carry
        i = perm[t]
        live = lane < lens_d[i]
        idx = jnp.where(live, jax.lax.dynamic_slice(ids_w, (starts[i],),
                                                    (width,)), d)
        x = jnp.where(live, jax.lax.dynamic_slice(x_w, (starts[i],),
                                                  (width,)), 0)
        grad = jnp.sum((w[idx] * x).astype(jnp.float32)) - 1.0
        old = alpha[i]
        new = jnp.clip(old.astype(jnp.float32) - grad / sq[i], 0.0,
                       C).astype(dt)
        return alpha.at[i].set(new), w.at[idx].add((new - old) * x), perm

    @jax.jit
    def epoch(alpha, w, key):
        perm = jax.random.permutation(key, n)
        alpha, w, _ = jax.lax.fori_loop(0, n, update, (alpha, w, perm))
        z = jnp.zeros((n,), jnp.float32).at[rows].add(
            (w[ids_d] * x_d).astype(jnp.float32))
        w32 = w[:d].astype(jnp.float32)
        gap = (w32 @ w32 + C * jnp.sum(jnp.maximum(1.0 - z, 0.0))
               - jnp.sum(alpha.astype(jnp.float32)))
        return alpha, w, gap

    return epoch


def _control(ctx, train, g0: float, target: float) -> Outcome:
    """One solve of the bfloat16 CSR control in the program's place, one
    epoch per dispatch and a read of its gap, to the target or for
    ``CONTROL_EPOCHS`` epochs; its answers go to the same checks."""
    import jax
    import jax.numpy as jnp

    from bench import gen

    now = time.perf_counter
    d = int(ctx.config["d"])
    host = (np.asarray(train.indices), np.asarray(train.values),
            train.indptr)
    epoch = _control_epoch(*host, d, float(ctx.config["C"]))
    n = train.indptr.size - 1
    alpha = jnp.zeros((n,), jnp.bfloat16)
    w = jnp.zeros((d + 1,), jnp.bfloat16)  # slot d: the masked lanes
    key = gen.key_from_seed(ctx.seed, 7)
    t0 = now()
    times, gaps = [0.0], [g0]
    for e in range(CONTROL_EPOCHS):
        alpha, w, g = epoch(alpha, w, jax.random.fold_in(key, e))
        gaps.append(float(g))
        times.append(now() - t0)
        if gaps[-1] <= target:
            break
    hit = crossing(times, gaps, target)
    solve = {"alpha": np.asarray(alpha, np.float32),
             "w_hat": np.asarray(w[:d], np.float32), "gap": gaps[-1],
             "gaps": gaps, "seconds": hit[0] if hit else times[-1]}
    print(f"bench: bfloat16 CSR reference control, gaps "
          f"{[round(g, 3) for g in gaps]}", file=ctx.log)
    return Outcome(end_to_end={"setup_s": t0 - ctx.t_process,
                               "solve_s": solve["seconds"]},
                   rec={"kind": "solve"},
                   checks=_check(ctx, host, [solve]), attempted=1,
                   failed=0, memory_peak_bytes=peak_bytes(
                       ctx.devices[:1]))
