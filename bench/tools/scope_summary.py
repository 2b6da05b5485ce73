#!/usr/bin/env python3
"""Reduce a solve cell's traced slice per scope of the program, and say
what the host was doing in the device's longest idle gaps.

    python3 bench/tools/scope_summary.py <dir holding an .xplane.pb> \\
        [--out summary.json]

Prints, for the slice a ``--trace 1`` run left under ``bench/.trace``:
the seconds the harness's reduction (``trace_reduce.extract`` and
``reduce``) and the scope reduction (``scopes.read_trace`` and
``summarize``) each take; each scope's complete runs, busy and mean run;
the busy time outside every run; the runs of each scope between
consecutive gap runs; the leaf ops that take most of each scope's time
and of the time outside every run; and the longest idle gaps, labelled
by the innermost ``bench.*`` or ``passcode.*`` host span, with the host
events that overlap each; and the device's program events beside the
host's enqueue and completion events, to see how the two timelines
line up.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/bench/", 1)[0])
from bench import scopes, trace_reduce  # noqa: E402


def host_events(pd, lo: float, hi: float, n: int = 8):
    """[[line, event, ns inside (lo, hi)]] of the host events that
    overlap the interval, longest overlap first."""
    acc: dict = collections.Counter()
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t > lo and s < hi:
                    acc[(ln.name, e.name[:80])] += min(t, hi) - max(s, lo)
    return [[k[0], k[1], v] for k, v in acc.most_common(n)]


# the runtime's events that bracket a program on the host: its enqueue,
# the completion callback, and the host's own spans
DISPATCH_MARKS = ("DoEnqueueProgram", "tpu::System::Execute=>Done",
                  "bench.", "passcode.")


def dispatch_events(pd, lo: float, hi: float):
    """The device's "XLA Modules" events and the host events that bracket
    each dispatch, over the slice: [[plane/line, name, start, end]] in
    start order, to set the device's timeline beside the host's."""
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:TPU:0")
        if not (dev or plane.name.startswith("/host:")):
            continue
        for ln in plane.lines:
            if dev and ln.name != "XLA Modules":
                continue
            for e in ln.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t < lo or s > hi:
                    continue
                if dev or e.name.startswith(DISPATCH_MARKS):
                    out.append([f"{plane.name}/{ln.name}", e.name[:60], s,
                                t])
    return sorted(out, key=lambda r: r[2])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--out")
    ap.add_argument("--gaps", type=int, default=6)
    args = ap.parse_args(argv)
    path = trace_reduce.find_xplane(args.trace_dir) or args.trace_dir

    t0 = time.perf_counter()
    old = trace_reduce.reduce(trace_reduce.extract(path),
                              window_span=scopes.WINDOW_SPAN)
    t_old = time.perf_counter() - t0
    t0 = time.perf_counter()
    trace = scopes.read_trace(path)
    window = scopes.slice_window(trace) if trace else None
    if window is None:
        print(f"{path}: no TPU op events in a traced slice",
              file=sys.stderr)
        return 1
    summ = scopes.summarize(trace["ops"], window)
    t_new = time.perf_counter() - t0

    runs = summ.pop("runs")
    per_op: dict = collections.defaultdict(collections.Counter)
    lo, hi = window
    spans_of = [(r[1], r[2], r[0]) for r in runs]
    j = 0
    for name, s, e, _ in scopes.leaf_ops(trace["ops"]):
        if e <= lo or s >= hi:
            continue
        while j < len(spans_of) and spans_of[j][1] < s:
            j += 1
        inside = j < len(spans_of) and spans_of[j][0] <= s
        per_op[spans_of[j][2] if inside else "outside runs"][
            trace_reduce.short(name, 100)] += (min(e, hi) - max(s, lo)) * 1e-9
    epochs = scopes.epoch_counts(runs)
    # per whole epoch, every event (containers too) of each scope, and
    # the update-scoped ``while`` events (one per round's row loop)
    gaps_at = [r for r in runs[1:-1] if r[0] == "passcode.gap"]
    epoch_events = []
    for a, b in zip(gaps_at, gaps_at[1:]):
        c = collections.Counter()
        for name, s, e, sc in trace["ops"]:
            if a[2] <= s < b[1]:
                c[sc or "unscoped"] += 1
                if sc == "passcode.update" and name.startswith("%while"):
                    c["update while"] += 1
        epoch_events.append(dict(c))

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    flat = [(name, s, e) for name, s, e, _ in trace["ops"]]
    gaps = sorted(trace_reduce.gaps(flat, window),
                  key=lambda g: g[0] - g[1])[:args.gaps]
    idle = [{"label": trace_reduce.label_at(trace["spans"], (s + e) / 2),
             "start_ns": s, "seconds": (e - s) * 1e-9,
             "host": host_events(pd, s, e)} for s, e in gaps]
    dispatches = dispatch_events(pd, lo, hi)
    out = {"reduce_s": {"trace_reduce": t_old, "scopes": t_new},
           "busy_s": old["busy_s"] if old else None,
           "window_s": old["window_s"] if old else None,
           "n_ops": trace["n_ops"], **summ,
           "epochs": epochs, "epoch_events": epoch_events,
           "top_ops": {k: v.most_common(6) for k, v in per_op.items()},
           "idle_gaps": idle, "dispatches": dispatches}
    print(json.dumps(out, indent=1, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
