#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell on the chip, to find its knee
once, and the rate its cell runs at: 0.8 × the knee.

The knee is the highest rate of the sweep at which, as at every lower
rate of it, the run keeps up:

* the 95th-percentile latency stays under the mix's ``latency_limit_ms``;
* no growing backlog: the second half of the window's p95 is at most
  ``BACKLOG_RATIO`` times the first half's;
* at most ``SHED_SHARE`` of the requests are shed (a request is shed
  when it waits past the mix's deadline; host stalls shed a few at any
  rate, a backlog sheds many).

One process, one set-up per rate, the rates in the order given; each
rate's reading, and then the knee, on standard error.

    python3 bench/tools/sweep.py --config rcv1 --traffic serve_poisson \\
        --seed 5 --seconds 30 --rates 1000,2000,3000
"""

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]
from bench import run as bench_run  # noqa: E402,F401  (sets the import path)
from bench import harness  # noqa: E402

BACKLOG_RATIO = 1.5
SHED_SHARE = 1e-3


def keeps_up(reading: dict, latency_limit_ms: float) -> bool:
    first, second = reading["p95_halves_ms"]
    return (reading["p95_ms"] <= latency_limit_ms
            and second <= BACKLOG_RATIO * first
            and reading["shed"] <= SHED_SHARE * reading["requests"])


def knee(readings: list, latency_limit_ms: float):
    """The highest rate below the first that does not keep up (None if
    the lowest does not)."""
    best = None
    for r in sorted(readings, key=lambda r: r["rate_rps"]):
        if not keeps_up(r, latency_limit_ms):
            break
        best = r["rate_rps"]
    return best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="rcv1")
    ap.add_argument("--traffic", default="serve_poisson")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import jax

    from repro.runtime import use_compile_cache

    devices = jax.devices()
    use_compile_cache(harness.ROOT)
    clock = harness.CompileClock()
    config = harness.load_json(os.path.join(harness.BENCH, "configs",
                                            args.config + ".json"))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         args.traffic + ".json"))
    name = f"{args.config}.{args.traffic}"
    limit = float(mix["latency_limit_ms"])
    readings = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.Cell(dict(name=name, config=args.config,
                                 traffic=args.traffic, chips=1),
                            config, dict(mix, rate_rps=rate), [], [])
        ctx = harness.Context(cell=cell, seed=args.seed,
                              seconds=args.seconds, trace=False,
                              devices=devices,
                              t_process=time.perf_counter(), clock=clock)
        out = harness.load_driver(cell.traffic["kind"]).run(ctx)
        r = {"rate_rps": rate, "requests": out.attempted,
             "shed": out.failed,
             "p95_ms": out.end_to_end["score_p95_ms"],
             "p95_halves_ms": out.rec["p95_halves_ms"],
             "goodput_rps": out.end_to_end["score_goodput_rps"],
             "rows_per_dispatch": out.rec["rows_per_dispatch"],
             "correct": all(c.ok for c in out.checks)}
        r["keeps_up"] = keeps_up(r, limit)
        readings.append(r)
        print("SWEEP " + json.dumps(r), file=sys.stderr, flush=True)
    k = knee(readings, limit)
    print("KNEE " + json.dumps({"knee_rps": k,
                                "rate_rps": None if k is None else 0.8 * k}),
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
