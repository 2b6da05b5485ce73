#!/usr/bin/env python3
"""Measure a cell's run-to-run spread on the chip, as the bounds are set:
sets of runs of ``bench/run.py`` on the same seeds, each run a process of
its own (this parent never touches JAX, so the child has the chip), then
per end-to-end metric and set the median and the interquartile spread
(Python's ``statistics.quantiles(values, n=4)``) as a share of the
median.  Extra seeds, traced or not, add readings of the output checks.
Every run's output lands under ``--out``.

    python3 bench/tools/spread.py --workload rcv1.solve --seconds 30 \\
        --seeds 11,12,13,14,15,16 --sets 2 --trace-seeds 17,18,19 \\
        --out out/spread/rcv1.solve
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one_run(args, seed: int, trace: int, tag: str) -> dict | None:
    cmd = [sys.executable, "bench/run.py", "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=args.timeout)
    except subprocess.TimeoutExpired:
        p = subprocess.CompletedProcess(cmd, 124, "",
                                        f"timed out after {args.timeout} s")
    wall = time.perf_counter() - t0
    with open(os.path.join(args.out, f"{tag}.out"), "w") as f:
        f.write(p.stdout)
    with open(os.path.join(args.out, f"{tag}.err"), "w") as f:
        f.write(p.stderr)
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    print(f"RUN {tag} seed {seed} trace {trace} rc {p.returncode} wall "
          f"{wall:.1f} s: " + (json.dumps({k: line[k] for k in (
              "correct", "attempted", "failed", "metrics", "checks")})
              if line else p.stderr[-1500:]), flush=True)
    return line


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--extra-seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--timeout", type=int, default=400)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets = []
    for k in range(args.sets if seeds else 0):
        sets.append([one_run(args, s, 0, f"set{k}_{s}") for s in seeds])
    extra = [one_run(args, int(s), 0, f"extra_{s}")
             for s in args.extra_seeds.split(",") if s]
    traced = [one_run(args, int(s), 1, f"trace_{s}")
              for s in args.trace_seeds.split(",") if s]
    for k, runs in enumerate(sets):
        ok = [r for r in runs if r]
        for name in sorted({m for r in ok for m in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in ok]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"SPREAD set {k} {name}: median {med!r} spread "
                      f"{sp!r} values {vals}", flush=True)
    every = [r for r in sum(sets, []) + extra + traced if r]
    print(f"CORRECT {sum(r['correct'] for r in every)} of {len(every)} "
          f"runs; {sum(r is None for r in sum(sets, []) + extra + traced)}"
          f" runs without a result", flush=True)
    for name in sorted({c for r in every for c in r["checks"]}):
        vals = [r["checks"][name]["value"] for r in every]
        print(f"CHECK {name}: max {max(vals)!r} over {len(vals)} runs, "
              f"limit {every[0]['checks'][name]['limit']!r}", flush=True)
    for r in traced:
        if r:
            print("TRACED " + json.dumps({"metrics": r["metrics"],
                                          "device": r["device"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
