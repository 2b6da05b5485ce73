#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is a workload of ``BENCHMARK.json``; its configuration, traffic
mix, driver and per-layer readers are found by name (``bench/harness.py``).
Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result; it never falls back to the CPU.  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The last
line of standard output is the JSON result; the last lines of standard
error are the numbers that decide ``correct``, each beside its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# JAX's persistent compile cache and libtpu's logs stay inside the
# checkout, at fixed paths, whatever the environment names: the program
# (repro.runtime.use_compile_cache) takes the cache directory from here
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, ".tpu_logs")


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``), so that
    ``setup_s`` counts the interpreter's start-up and imports too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


# the process's start on the perf_counter clock, read before any import
T_PROCESS = time.perf_counter() - process_age()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             clock, t_process: float, control=None,
             log=sys.stderr) -> dict:
    """Drive one run of ``cell`` on ``devices`` and return its result
    object (the chip check is the caller's)."""
    from bench import harness

    ctx = harness.Context(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        devices=devices, t_process=t_process, clock=clock,
        trace_dir=os.path.join(BENCH, ".trace", cell.name),
        control=control, log=log)
    driver = harness.load_driver(cell.traffic["kind"])
    outcome = driver.run(ctx)
    used = devices[:cell.chips]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    breakdown = None
    tr = outcome.rec.get("trace")
    if trace:
        if tr is None:
            raise harness.HarnessError(
                "the trace holds no device operation in the window")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    return harness.result_line(cell, outcome, trace=trace, device=device,
                               breakdown=breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    try:
        cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    except (harness.HarnessError, OSError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    t_devices = time.perf_counter() - T_PROCESS
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devices[0].platform!r});"
              f" the benchmark runs only on the chip", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    try:
        harness.load_peaks(devices[0].device_kind)
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    from repro.runtime import use_compile_cache

    cache = use_compile_cache(ROOT)
    # every program, however quick to compile, goes to the cache, so that
    # a run's set-up after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = harness.CompileClock()
    print(f"bench: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind} ({devices[0].platform}), jax "
          f"{jax.__version__}, compile cache {cache}", file=sys.stderr)
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices,
                    clock=clock, t_process=T_PROCESS)
    sec, events, hits = clock.read()
    print(f"bench: compile {sec:.3f} s over {events} events, {hits} "
          f"persistent-cache hits; {t_devices:.3f} s from process start "
          f"to the devices", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
