"""Window driver for solve traffic: repeated solves from α = 0 to a
relative duality gap, through the program's prepared-pipeline API.

Set-up: the configuration's data from the seed (``bench/gen.py``), one
``prepare_solver`` with the defaults users get (``use_kernel="auto"``,
``block_size=64``, ``gap_every=1``, ``record=True``) on a ``data`` mesh
of the cell's chips, and the compile of the one-epoch pipeline.

Window: per solve, ``init_pipeline_state``; then one one-epoch dispatch
per epoch and a read of that epoch's recorded gap, until the gap falls to
``target_rel_gap`` of the zero-start gap g(0) = Σ ℓ(0) (closed form, on
the host); then ``finalize_state``.  Each solve draws its own update
order from the seed.  A solve's time is where its gap crosses the
target, interpolated on log-gap between the two epochs that bracket it.
A solve still running when the window closes is dropped, unless no solve
has ended yet: then it runs on to its end, so that every run reports one.
A solve that reaches ``epoch_cap`` without the target has failed.

A ``--trace 1`` run traces a slice of ``trace_seconds`` centred on the
first solve's second epoch boundary (its first epoch's length, measured,
stands for the second's), so that the slice holds the host's round trip
between two epochs however long an epoch is.

Parameters (``bench/traffic/<mix>.json``): ``data`` (devices on the
``data`` mesh axis), ``target_rel_gap``, ``epoch_cap``, ``trace_seconds``
(the traced slice of a ``--trace 1`` window), ``limits`` (the output
checks' limits).

The control (``bench/tools/control.py``) puts the plain bfloat16
reference solver of ``bench/control_dcd.py`` in the program's place.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import gen, reference
from bench.harness import Check, Outcome, peak_bytes, span_totals


def crossing(times, gaps, target: float):
    """(time, epochs) at which ``gaps`` first reaches ``target``,
    linear in log-gap between the bracketing records; ``times[0]`` and
    ``gaps[0]`` are the start (epoch 0).  None if it never does."""
    for e in range(1, len(gaps)):
        if gaps[e] <= target:
            g_a, g_b = gaps[e - 1], gaps[e]  # g_a > target >= g_b
            frac = 1.0 if g_b <= 0 else (
                (math.log(g_a) - math.log(target))
                / (math.log(g_a) - math.log(g_b)))
            t = times[e - 1] + frac * (times[e] - times[e - 1])
            return t, (e - 1) + frac
    return None


def solve_seed(seed: int, j: int) -> int:
    """The update-order seed of the run's j-th solve (31 bits, as the
    program's ``PRNGKey`` takes it)."""
    return gen.seed_words(seed, 7, j)[0] >> 1


def _program_loss(cfg: dict):
    from repro.core.duals import Hinge

    return {"hinge": Hinge}[cfg["loss"]](C=float(cfg["C"]))


def _compile_epoch(sharded, setup, cap: int):
    fn = sharded.build_pipeline(setup, epochs=1, total_epochs=cap,
                                segmented=True)
    st = sharded.init_pipeline_state(setup, total_epochs=cap)
    return fn.lower(setup.X, setup.sq_norms, st).compile()


def run(ctx) -> Outcome:
    import jax

    from repro.core import sharded
    from repro.data.sparse import EllMatrix
    from repro.dist.mesh import make_mesh

    cfg, mix, spans = ctx.config, ctx.traffic, ctx.spans
    chips = int(mix["data"])
    if chips != ctx.cell.chips:
        raise ValueError(f"mix asks for data={chips}, the cell for "
                         f"{ctx.cell.chips} chips")
    d = int(cfg["d"])
    cap = int(mix["epoch_cap"])
    g0 = reference.zero_gap(cfg)
    target = float(mix["target_rel_gap"]) * g0
    now = time.perf_counter

    with spans("bench.setup.data"):
        train = gen.make_problem(cfg, ctx.seed, test=False)[0]
        jax.block_until_ready(train.values)
    if ctx.control == "bf16":
        return _control(ctx, train, g0, target)
    mesh = make_mesh((chips,), ("data",), devices=ctx.devices[:chips])
    t0 = now()
    with spans("bench.setup.prepare"):
        setup = sharded.prepare_solver(
            EllMatrix(train.indices, train.values, d), _program_loss(cfg),
            mesh=mesh, use_kernel="auto", block_size=64, gap_every=1,
            record=True, seed=solve_seed(ctx.seed, 0))
        jax.block_until_ready((setup.X, setup.sq_norms))
    prepare_s = now() - t0
    with spans("bench.setup.compile"):
        epoch = _compile_epoch(sharded, setup, cap)
        # the small programs every solve calls, compiled here
        st = sharded.init_pipeline_state(setup, total_epochs=cap)
        res = sharded.finalize_state(setup, st, epochs=cap)
        np.asarray(st["gaps"]), np.asarray(res.alpha), np.asarray(res.w_hat)
    setup_s = now() - ctx.t_process
    compile_s, compile_events, _ = ctx.clock.read()

    solves, capped = [], 0
    slice_s = float(mix["trace_seconds"])
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
    window_compiles = ctx.clock.read()[1] - compile_events
    mem = peak_bytes(ctx.devices[:chips])

    rec = {"kind": "solve", "prepare_s": prepare_s, "compile_s": compile_s,
           "window_compiles": window_compiles,
           "updates_per_device": setup.n_blocks * setup.block_size,
           "solves": len(solves), "engine": sharded.engine_name(setup)}
    if solves:
        rec["epochs_to_target"] = float(np.mean([s["epochs"]
                                                 for s in solves]))
    rec["trace"] = ctx.stop_trace()
    if ctx.trace:
        rec.update(_layer_dispatches(ctx, sharded, setup, epoch, cap))

    del setup, epoch, st, res
    checks = _check(ctx, train, solves)
    # with no finished solve, the time spent without reaching the target
    # stands in (the run is then not correct)
    e2e = {"setup_s": setup_s,
           "solve_s": (float(np.mean([s["seconds"] for s in solves]))
                       if solves else now() - t_w)}
    print(f"bench: engine {rec['engine']}; {len(solves)} solves, "
          f"{capped} capped, gaps of the "
          f"first {[round(g, 3) for g in solves[0]['gaps']] if solves else []}"
          f", {window_compiles} compiles in the window; solve seconds "
          f"{[round(s['seconds'], 4) for s in solves]}; set-up spans "
          f"{ {k: round(v, 3) for k, v in span_totals(spans, 'bench.setup').items()} }",
          file=ctx.log)
    return Outcome(end_to_end=e2e, rec=rec, checks=checks,
                   attempted=len(solves) + capped, failed=capped,
                   correct_extra=bool(solves),
                   memory_peak_bytes=mem)


def _layer_dispatches(ctx, sharded, setup, epoch, cap: int,
                      repeats: int = 2) -> dict:
    """One-epoch dispatches from α = 0 with the gap recorded and without,
    alternated, each timed by the host clock to its end with the profiler
    off: the gap's cost is the difference of their fastest times, the
    engine's the second's.  The unrecorded pipeline is compiled here, in
    traced runs only."""
    import jax

    now = time.perf_counter
    bare = setup._replace(record=False)
    epoch_bare = _compile_epoch(sharded, bare, cap)
    best = {"record": math.inf, "bare": math.inf}
    for _ in range(repeats):
        for name, fn, s in (("record", epoch, setup),
                            ("bare", epoch_bare, bare)):
            st = sharded.init_pipeline_state(s, total_epochs=cap)
            jax.block_until_ready(st)
            with ctx.spans("bench.epoch_" + name):
                t0 = now()
                jax.block_until_ready(fn(setup.X, setup.sq_norms, st))
                best[name] = min(best[name], now() - t0)
    upd = setup.n_blocks * setup.block_size
    return {"epoch_record_s": best["record"], "epoch_bare_s": best["bare"],
            "gap_ms": (best["record"] - best["bare"]) * 1e3,
            "update_us": best["bare"] / upd * 1e6}


def _check(ctx, train, solves) -> list:
    """Each finished solve against the host float64 reference; the worst
    over the solves of each number, beside its limit."""
    lim = ctx.traffic["limits"]
    if not solves:
        return []  # no answer came: not correct
    ids = np.asarray(train.indices)
    vals = np.asarray(train.values)
    loss = reference.loss_of(ctx.config)
    d = int(ctx.config["d"])
    worst = {"inv": 0.0, "box": 0.0, "gap": 0.0}
    for s in solves:
        got = reference.solve_checks(ids, vals, d, loss, s["alpha"],
                                     s["w_hat"], s["gap"])
        for key in worst:
            worst[key] = max(worst[key], got[key])
    return [Check(key, worst[key], float(lim[key])) for key in worst]


def _control(ctx, train, g0: float, target: float) -> Outcome:
    """One solve of the bfloat16 reference in the program's place, one
    epoch per dispatch and a read of its gap, to the target or for
    ``control_dcd.EPOCHS`` epochs; its answers go to the same checks."""
    import jax

    from bench import control_dcd

    now = time.perf_counter
    epoch = control_dcd.make_epoch(train.indices, train.values,
                                   float(ctx.config["C"]))
    alpha, w = control_dcd.zero_state(train.indices.shape[0],
                                      int(ctx.config["d"]))
    key = gen.key_from_seed(ctx.seed, 7)
    t0 = now()
    times, gaps = [0.0], [g0]
    for e in range(control_dcd.EPOCHS):
        alpha, w, g = epoch(alpha, w, jax.random.fold_in(key, e))
        gaps.append(float(g))
        times.append(now() - t0)
        if gaps[-1] <= target:
            break
    hit = crossing(times, gaps, target)
    solve = {"alpha": np.asarray(alpha, np.float32),
             "w_hat": np.asarray(w, np.float32), "gap": gaps[-1],
             "gaps": gaps, "seconds": hit[0] if hit else times[-1]}
    print(f"bench: bfloat16 reference control, gaps "
          f"{[round(g, 3) for g in gaps]}", file=ctx.log)
    return Outcome(end_to_end={"setup_s": t0 - ctx.t_process,
                               "solve_s": solve["seconds"]},
                   rec={"kind": "solve"},
                   checks=_check(ctx, train, [solve]), attempted=1,
                   failed=0, memory_peak_bytes=peak_bytes(
                       ctx.devices[:1]))
