#!/usr/bin/env python3
"""Read the output checks of a cell's lower-precision control on the chip.

The control is the reference put in the program's place and computed
one step below the configuration's float32, in bfloat16: solve cells run
the plain dual coordinate descent of ``bench/control_dcd.py`` on the
cell's data; serving cells put the reference's sparse dot, with bfloat16
products, in the engine's place.  Both are held to the same checks.  The benchmark's own
runs never run it.  Each seed's checks go to standard error as one JSON
line; every one of them has to come out not correct.

    python3 bench/tools/control.py --workload rcv1.solve \\
        --seeds 21,22,23 --seconds 5
"""

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]
from bench import run as bench_run  # noqa: E402  (sets the import path)
from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    import jax

    from repro.runtime import use_compile_cache

    devices = jax.devices()
    use_compile_cache(harness.ROOT)
    clock = harness.CompileClock()
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = bench_run.run_cell(cell, seed=seed, seconds=args.seconds,
                                  trace=False, devices=devices,
                                  clock=clock, t_process=time.perf_counter(),
                                  control="bf16")
        print("CONTROL " + json.dumps({"seed": seed,
                                       "correct": line["correct"],
                                       "checks": line["checks"]}),
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
