"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes
are ``/device:TPU:<i>``; their op-level line is "XLA Ops" (or, failing
that, "XLA Modules").  Host spans are the harness's own
``TraceAnnotation``s, named ``bench.*``, on the host plane.  All times
are nanoseconds on the profiler's clock; the pure functions below take
plain (name, start, end) tuples so that tests can drive them with a
small recorded or hand-made trace.
"""

from __future__ import annotations

import glob
import os

OP_LINES = ("XLA Ops", "XLA Modules")
# the TPU profiler keeps at most this many events per device (seen on a
# v5e: a longer trace stopped at exactly 6,291,456 = 6 * 2^20); a trace
# that reaches it covers only the start of the window
EVENT_CAP = 6 * 2**20
COLLECTIVE_MARKS = ("all-reduce", "allreduce", "all_reduce")


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns)
             + float(e.duration_ns)) for e in line.events]


def short(name: str, n: int = 120) -> str:
    """An op's HLO text cut to a readable length."""
    return name if len(name) <= n else name[:n] + "..."


def extract(path: str) -> dict:
    """{"devices": {i: [(op, start, end)]}, "spans": [(name, start, end)],
    "op_line": the line the ops came from, "events": {i: events on the
    device's plane}} from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, used, counts = {}, [], None, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            counts[idx] = sum(1 for ln in plane.lines for _ in ln.events)
            for want in OP_LINES:
                if want in lines:
                    devices[idx] = _events(lines[want])
                    used = want
                    break
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith("bench.")]
    return {"devices": devices, "spans": spans, "op_line": used,
            "events": counts}


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for _, s, e in intervals
            if e > lo and s < hi]


def union_length(pairs) -> float:
    """Length of the union of (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(pairs):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy(ops, window) -> float:
    """Nanoseconds of ``window`` in which some op ran."""
    return union_length(clip(ops, *window))


def share_of(ops, window, marks=COLLECTIVE_MARKS) -> float:
    """Fraction of ``window`` in which an op whose name holds one of
    ``marks`` ran."""
    lo, hi = window
    hit = [op for op in ops if any(m in op[0].lower() for m in marks)]
    return union_length(clip(hit, lo, hi)) / max(hi - lo, 1e-9)


def top_ops(ops, window, n: int = 10):
    """[[op name, seconds]] of the ops that took most device time in the
    window, summed by name."""
    acc: dict = {}
    for name, s, e in ops:
        s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            acc[name] = acc.get(name, 0.0) + (e - s)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[short(k), v * 1e-9] for k, v in top]


def gaps(ops, window):
    """(start, end) of each idle interval of ``window``."""
    lo, hi = window
    out, t = [], lo
    for s, e in sorted(clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(spans, t: float) -> str:
    """The innermost harness span covering time ``t`` ("host" if none)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host"


def idle_gaps(ops, spans, window, n: int = 10):
    """[[label, seconds]] of the ``n`` longest idle gaps, each labelled
    by what the host was doing at its midpoint."""
    g = sorted(gaps(ops, window), key=lambda p: p[0] - p[1])[:n]
    return [[label_at(spans, (s + e) / 2), (e - s) * 1e-9] for s, e in g]


def span_window(spans, name: str):
    """(start, end) of the span ``name`` (the first one)."""
    for nm, s, e in spans:
        if nm == name:
            return (s, e)
    return None


def reduce(trace: dict, window_span: str = "bench.window") -> dict | None:
    """Device numbers over the harness's traced window: busy and window
    seconds averaged over the devices, device 0's collective share, the
    top ops and the longest idle gaps.  None when the trace holds no
    device ops or no window span."""
    window = span_window(trace["spans"], window_span)
    devs = trace["devices"]
    if window is None or not devs or not any(devs.values()):
        return None
    truncated = any(n >= EVENT_CAP for n in trace.get("events", {}).values())
    if truncated:
        # the profiler stopped recording: reduce over the part it covers
        covered = min(max(e for _, _, e in ops) for ops in devs.values()
                      if ops)
        window = (window[0], min(window[1], covered))
    w_ns = window[1] - window[0]
    busy_ns = [busy(ops, window) for ops in devs.values()]
    d0 = devs[min(devs)]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "window_s": w_ns * 1e-9,
        "allreduce_share": share_of(d0, window),
        "device_ops": top_ops(d0, window),
        "idle_gaps": idle_gaps(d0, trace["spans"], window),
        "op_line": trace.get("op_line"),
        "n_ops": len(d0),
        "truncated": truncated,
    }
