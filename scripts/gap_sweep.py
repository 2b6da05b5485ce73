"""Time the duality gap's two sparse products, XLA's scatter and gather
against the one-hot MXU pass (``repro.kernels.gap_onehot``), over primal
widths d_run = 128·R: the sweep that fixes ``gap_onehot.R_MAX``.  Needs
a TPU: it exits with status 2 anywhere else.

    PYTHONPATH=src python3 scripts/gap_sweep.py --out gap_sweep.jsonl

Each width draws ``--rows`` rows of ``--k`` ids, Zipf-weighted
(p_j ∝ j^-0.9, the benchmark's law) over d = 128·R − 1 features, N(0, 1)
values and α in [0, 1), on the device from ``--seed``.  Each product is
timed by the host clock to ``block_until_ready``, the best of ``--reps``
calls after a warm-up, and checked against the XLA op: ``rmv_rel`` and
``mv_rel`` are the norm of the difference over the norm of the XLA
result.  ``--ragged-rows`` also checks the packed-slot call as the
ragged gap makes it at rcv1's width.  One JSON line per measurement.
XLA's scatter and gather take minutes each to compile at the default
``--rows`` on a TPU v5e host (the passes about a second): give a
re-check of ``rmv_rel``/``mv_rel`` a few thousand rows.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gap_onehot import onehot_mv, onehot_rmv

RCV1_D = 47_236  # its R is 370


def _draw(seed: int, rows: int, k: int, d: int):
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jnp.arange(1, d + 1, dtype=jnp.float32) ** -0.9
    cdf = jnp.cumsum(p) / jnp.sum(p)
    u = jax.random.uniform(ka, (rows, k))
    cols = jnp.minimum(jnp.searchsorted(cdf, u), d - 1).astype(jnp.int32)
    vals = jax.random.normal(kb, (rows, k), jnp.float32)
    alpha = jax.random.uniform(kc, (rows,), jnp.float32)
    return jax.block_until_ready((cols, vals, alpha))


def _best(fn, args, reps: int):
    out = jax.block_until_ready(fn(*args))  # compile and warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def measure(cols, vals, alpha, d_run: int, reps: int) -> dict:
    """Both engines' ns per entry and the pass's distance from XLA's."""
    entries = cols.size
    pallas_rmv = jax.jit(lambda c, v, a: onehot_rmv(c, v, d_run, a))
    pallas_mv = jax.jit(lambda c, v, w: onehot_mv(c, v, w, row_sum=True))
    xla_rmv = jax.jit(lambda c, v, a: jnp.zeros(
        (d_run,), jnp.float32).at[c].add(a[:, None] * v))
    xla_mv = jax.jit(lambda c, v, w: jnp.sum(w[c] * v, axis=1))
    rec = {"d_run": d_run, "R": d_run // 128, "rows": cols.shape[0],
           "k": cols.shape[1]}
    t, w = _best(pallas_rmv, (cols, vals, alpha), reps)
    rec["pallas_rmv_ns"] = t / entries * 1e9
    t, w_ref = _best(xla_rmv, (cols, vals, alpha), reps)
    rec["xla_rmv_ns"] = t / entries * 1e9
    t, z = _best(pallas_mv, (cols, vals, w_ref), reps)
    rec["pallas_mv_ns"] = t / entries * 1e9
    t, z_ref = _best(xla_mv, (cols, vals, w_ref), reps)
    rec["xla_mv_ns"] = t / entries * 1e9
    rec["rmv_rel"], rec["mv_rel"] = _rel(w, w_ref), _rel(z, z_ref)
    return rec


def ragged_check(seed: int, rows: int, d: int) -> dict:
    """The packed-slot call as the ragged gap makes it ((slots / 128,
    128) view, each 16-slot group's scale, per-slot products) against
    XLA's scatter and gather; ``mv_mismatch`` counts the products that
    differ from XLA's at all."""
    cols, vals, _ = _draw(seed, rows, 128, d)
    d_run = -(-(d + 1) // 128) * 128
    ag = jax.random.uniform(jax.random.PRNGKey(seed + 1), (rows, 8))
    w = onehot_rmv(cols, vals, d_run, ag)
    u = jnp.repeat(ag, 16, axis=1) * vals
    w_ref = jnp.zeros((d_run,), jnp.float32).at[cols].add(u)
    prod = onehot_mv(cols, vals, w_ref, row_sum=False)
    return {"ragged_rows": rows, "d_run": d_run, "rmv_rel": _rel(w, w_ref),
            "mv_mismatch": int(jnp.sum(prod != w_ref[cols] * vals))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=131_072)
    ap.add_argument("--k", type=int, default=73)
    ap.add_argument("--widths", default="64,128,256,370,512,768,1024,"
                                        "1536,2048,3072,4096")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ragged-rows", type=int, default=0,
                    help="also check the packed-slot call at rcv1's width")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"gap_sweep: needs a TPU, found {dev.platform}: off the "
              f"chip the pass runs in interpret mode, whose timings say "
              f"nothing of the chip", file=sys.stderr)
        sys.exit(2)
    lines = []

    def emit(rec):
        rec["device"] = dev.device_kind
        line = json.dumps(rec)
        print(line, flush=True)
        lines.append(line)

    for r in (int(x) for x in args.widths.split(",") if x):
        d = RCV1_D if r == 370 else 128 * r - 1
        cols, vals, alpha = _draw(args.seed, args.rows, args.k, d)
        emit(measure(cols, vals, alpha, 128 * r, args.reps))
    if args.ragged_rows:
        emit(ragged_check(args.seed, args.ragged_rows, RCV1_D))
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
