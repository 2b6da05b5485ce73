"""Per-layer device time of the solver, read from the layers the program
names inside its compiled epoch.

``repro.core.sharded`` wraps each layer of the epoch in a
``jax.named_scope``: ``passcode.perm`` (the update-order draw),
``passcode.update`` (the block engine), ``passcode.merge`` (the round's
Δw, psum and fold) and ``passcode.gap`` (the gap and its records).  The
scope reaches the ``op_name`` of every compiled instruction it holds.  A
TPU trace's op events carry only the instruction's HLO text, so the
op_name is looked up in the compiled module's HLO proto, which the
profiler stores on the trace's ``/host:metadata`` plane; each op event is
matched to its module by the "XLA Modules" event that holds it.  Both are
read from the ``.xplane.pb`` itself, once per distinct op, with a small
protobuf reader below (no generated protobuf classes needed).

A **run** is a maximal sequence of leaf ops on device 0, in start order,
that share a scope.  Container events (a ``while`` or ``conditional``
whose interval holds other events) and unscoped ops neither count nor
break a run.  A run is **complete** when an op of another scope lies
before it and after it inside the traced slice; only complete runs are
averaged, so a run cut by the slice's edge never is.

The per-layer readers (``bench/metrics/{update_device_us,merge_us,
perm_ms,gap_device_ms}.py``) call ``mean_run_s``; the trace is the one
the run's driver reduced into ``rec["trace"]`` (found by the newest
``.xplane.pb`` under ``bench/.trace`` whose op count and slice match it),
read once per run.  A trace of a program without the scopes gives no run,
and the readers then report nothing.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from bench import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
PREFIX = "passcode."
SCOPES = ("passcode.perm", "passcode.update", "passcode.merge",
          "passcode.gap")
METADATA_PLANE = "/host:metadata"
WINDOW_SPAN = "bench.traced"
# the solve driver's block size (bench/drivers/solve.py passes
# block_size=64 to prepare_solver); a run record that names its own wins
SOLVE_BLOCK_SIZE = 64


# ------------------------------------------------ protobuf wire format ----


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its (start, end)."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def hlo_modules(buf: bytes) -> dict:
    """{module name as the "XLA Modules" events give it: (start, end) of
    its serialized ``HloProto``} from the metadata plane of an XSpace
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4;
    XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6)."""
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, metas = None, []
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, pv)
            elif pf == 4:
                metas.append(pv)
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            for ef, ev in _fields(buf, *entry):
                if ef != 2:  # map entry: key = 1, value = 2
                    continue
                em_name, proto = None, None
                for mf, mv in _fields(buf, *ev):
                    if mf == 2:
                        em_name = _text(buf, mv)
                    elif mf == 5:
                        for sf, sv in _fields(buf, *mv):
                            if sf == 6:
                                proto = sv
                if em_name and proto:
                    out[em_name] = proto
    return out


def scope_of(op_name: str) -> str | None:
    """The innermost ``passcode.*`` component of an HLO op_name."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return None


def instruction_scopes(buf: bytes, proto) -> dict:
    """{instruction name: scope or None} of one serialized ``HloProto``
    (.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    for f, mod in _fields(buf, *proto):
        if f != 1:
            continue
        for cf, comp in _fields(buf, *mod):
            if cf != 3:
                continue
            for kf, inst in _fields(buf, *comp):
                if kf != 2:
                    continue
                name, scope = None, None
                for nf, nv in _fields(buf, *inst):
                    if nf == 1:
                        name = _text(buf, nv)
                    elif nf == 7:
                        for of, ov in _fields(buf, *nv):
                            if of == 2:
                                scope = scope_of(_text(buf, ov))
                if name:
                    out[name] = scope
    return out


def instruction_name(event_name: str) -> str:
    """``fusion.75`` out of an op event's HLO text, ``%fusion.75 = ...``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


# ----------------------------------------------------------- the trace ----


def _device0(pd):
    best = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if best is None or idx < best[0]:
                best = (idx, plane)
    return None if best is None else best[1]


def read_trace(path: str) -> dict | None:
    """Device 0's ops with their scopes and the host spans of an
    ``.xplane.pb``: {"ops": [(name, start, end, scope)], "spans": [(name,
    start, end)] (``bench.*`` and ``passcode.*``), "op_line", "n_ops",
    "events"}; times in ns on the profiler's clock.  None without a TPU
    device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    plane = _device0(pd)
    if plane is None:
        return None
    with open(path, "rb") as f:
        buf = f.read()
    protos = hlo_modules(buf)
    lines = {ln.name: ln for ln in plane.lines}
    op_line = next((w for w in trace_reduce.OP_LINES if w in lines), None)
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in lines["XLA Modules"].events) \
        if "XLA Modules" in lines else []
    maps: dict = {}
    resolved: dict = {}
    canon: dict = {}  # one string per distinct op, not per event
    ops = []
    if op_line == "XLA Ops":
        raw = sorted((e.start_ns, e.duration_ns, e.name)
                     for e in lines[op_line].events)
        m = 0
        for s, dur, name in raw:
            while m < len(modules) and modules[m][1] < s:
                m += 1
            mod = modules[m][2] if (m < len(modules)
                                    and modules[m][0] <= s) else None
            key = (mod, name)
            scope = resolved.get(key, False)
            if scope is False:
                if mod not in maps:
                    maps[mod] = (instruction_scopes(buf, protos[mod])
                                 if mod in protos else {})
                scope = maps[mod].get(instruction_name(name))
                resolved[key] = scope
            ops.append((canon.setdefault(name, name), float(s),
                        float(s) + float(dur), scope))
    spans = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                spans += [(e.name, float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
                          for e in ln.events
                          if e.name.startswith(("bench.", PREFIX))]
    return {"ops": ops, "spans": spans, "op_line": op_line,
            "n_ops": len(ops) if op_line == "XLA Ops" else None,
            "events": sum(1 for ln in plane.lines for _ in ln.events)}


def slice_window(trace: dict, window_span: str = WINDOW_SPAN):
    """The traced slice as ``trace_reduce.reduce`` takes it: the window
    span, cut where device 0's events stop if the profiler's buffer
    filled."""
    window = trace_reduce.span_window(trace["spans"], window_span)
    if window is None or not trace["ops"]:
        return None
    if trace.get("events", 0) >= trace_reduce.EVENT_CAP:
        window = (window[0], min(window[1],
                                 max(e for _, _, e, _ in trace["ops"])))
    return window


# ------------------------------------------------------------- runs ----


def _arrays(ops):
    """(starts, ends, scope codes, scope names, order) of ``ops`` sorted
    by start, the longer event first at equal starts; a code indexes the
    names, -1 is unscoped."""
    code: dict = {}
    starts = np.array([o[1] for o in ops], np.float64)
    ends = np.array([o[2] for o in ops], np.float64)
    codes = np.array([-1 if o[3] is None else code.setdefault(
        o[3], len(code)) for o in ops], np.int64)
    names = sorted(code, key=code.get)
    order = np.lexsort((-ends, starts))
    return starts[order], ends[order], codes[order], names, order


def _leaf_mask(starts, ends):
    """True for leaf events: not an event whose interval holds the next
    one (a container)."""
    leaf = np.ones(len(starts), bool)
    if len(starts) > 1:
        s1, e1 = starts[1:], ends[1:]
        same = (s1 == starts[:-1]) & (e1 == ends[:-1])
        leaf[:-1] = ~((s1 < ends[:-1]) & (e1 <= ends[:-1]) & ~same)
    return leaf


def leaf_ops(ops):
    """``ops`` in start order without container events (an event whose
    interval holds a later one; at equal starts the longer event is the
    container)."""
    if not ops:
        return []
    starts, ends, _, _, order = _arrays(ops)
    keep = order[_leaf_mask(starts, ends)]
    return [ops[i] for i in keep]


class Busy:
    """The device's busy time (the union of every event, containers
    included, as ``idle_share`` counts it) inside ``window``, queried
    over any interval."""

    def __init__(self, starts, ends, window):
        lo, hi = window
        inside = (ends > lo) & (starts < hi)
        s = np.maximum(starts[inside], lo)
        e = np.minimum(ends[inside], hi)
        order = np.argsort(s, kind="stable")
        s, e = s[order], e[order]
        reach = np.maximum.accumulate(e) if len(e) else e
        new = np.ones(len(s), bool)
        new[1:] = s[1:] > reach[:-1]
        first = np.flatnonzero(new)
        self.starts = s[first]
        self.ends = np.maximum.reduceat(e, first) if len(first) else e[:0]
        self.before = np.concatenate(
            [[0.0], np.cumsum(self.ends - self.starts)])

    def upto(self, t):
        """Busy ns of the window before ``t`` (a number or an array)."""
        t = np.asarray(t, np.float64)
        if not len(self.starts):
            return np.zeros_like(t)
        i = np.searchsorted(self.starts, t, side="right")
        j = np.maximum(i - 1, 0)
        part = np.minimum(t, self.ends[j]) - self.starts[j]
        return np.where(i == 0, 0.0, self.before[j] + part)

    def between(self, s, e):
        return self.upto(e) - self.upto(s)

    @property
    def total(self) -> float:
        return float(self.before[-1])


def scope_runs(ops, window):
    """([[scope, start, end, busy ns, n ops]], Busy) of the runs of
    scoped leaf ops inside ``window``, in order: a run spans its first
    op's start to its last op's end (clipped to the window), and its
    busy is the device's busy time in that span, so that the row loop's
    control between its ops counts.  The first and the last run are not
    complete."""
    lo, hi = window
    if not ops:
        return [], Busy(np.zeros(0), np.zeros(0), window)
    starts, ends, codes, names, _ = _arrays(ops)
    busy = Busy(starts, ends, window)
    pick = _leaf_mask(starts, ends) & (codes >= 0) & (ends > lo) \
        & (starts < hi)
    s = np.maximum(starts[pick], lo)
    e = np.minimum(ends[pick], hi)
    c = codes[pick]
    if not len(c):
        return [], busy
    first = np.flatnonzero(np.concatenate([[True], c[1:] != c[:-1]]))
    r_start = s[first]
    r_end = np.maximum.reduceat(e, first)
    r_n = np.diff(np.concatenate([first, [len(c)]]))
    r_busy = busy.between(r_start, r_end)
    runs = [[names[k], float(a), float(b), float(u), int(n)]
            for k, a, b, u, n in zip(c[first], r_start, r_end, r_busy,
                                     r_n)]
    return runs, busy


def summarize(ops, window) -> dict:
    """{"scopes": {scope: {"complete_runs", "busy_s", "mean_run_s"}},
    "unscoped_busy_s", "busy_s", "runs"}: the busy of each scope's
    complete runs, the slice's busy time outside every run, and the
    slice's busy time."""
    runs, busy = scope_runs(ops, window)
    acc = {sc: [0, 0.0] for sc in SCOPES}
    for scope, _, _, busy_ns, _ in runs[1:-1]:
        a = acc.setdefault(scope, [0, 0.0])
        a[0] += 1
        a[1] += busy_ns
    in_runs = sum(r[3] for r in runs)
    return {"scopes": {sc: {"complete_runs": n, "busy_s": b * 1e-9,
                            "mean_run_s": (b / n * 1e-9) if n else None}
                       for sc, (n, b) in acc.items()},
            "unscoped_busy_s": (busy.total - in_runs) * 1e-9,
            "busy_s": busy.total * 1e-9, "runs": runs}


def epoch_counts(runs) -> list:
    """Runs of each scope between consecutive complete ``passcode.gap``
    runs: [{scope: count}], one per epoch the slice holds whole."""
    marks = [i for i, r in enumerate(runs[1:-1], 1)
             if r[0] == "passcode.gap"]
    out = []
    for a, b in zip(marks, marks[1:]):
        c: dict = {}
        for r in runs[a + 1:b]:
            c[r[0]] = c.get(r[0], 0) + 1
        out.append(c)
    return out


# ------------------------------------------------------ for a run rec ----

_CACHE: dict = {}


def newest_xplane(root: str = os.path.join(BENCH, ".trace")) -> str | None:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def summary_for(rec: dict) -> dict | None:
    """The scope summary of the trace the driver reduced into
    ``rec["trace"]``; None when there is none, or the newest trace on
    disk is not that one."""
    tr = rec.get("trace")
    if not tr or rec.get("kind") != "solve":
        return None
    path = newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = _summary_of(path, tr)
    return _CACHE[key]


def _summary_of(path: str, tr: dict) -> dict | None:
    trace = read_trace(path)
    if trace is None or trace["n_ops"] != tr.get("n_ops"):
        return None
    window = slice_window(trace)
    if window is None or abs((window[1] - window[0]) * 1e-9
                             - tr["window_s"]) > 1e-6:
        return None
    out = summarize(trace["ops"], window)
    del out["runs"]
    return out


def mean_run_s(rec: dict, scope: str) -> float | None:
    """Mean device-busy seconds of the complete runs of ``scope``."""
    try:
        s = summary_for(rec)
    except Exception:  # a reader reports nothing rather than fail the run
        return None
    if not s or scope not in s["scopes"]:
        return None
    return s["scopes"][scope]["mean_run_s"]


def block_size(rec: dict) -> int:
    return int(rec.get("block_size") or SOLVE_BLOCK_SIZE)
