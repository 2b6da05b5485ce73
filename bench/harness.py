"""The data-driven core of the benchmark: finds a cell's files by name,
runs its driver, reads its per-layer metrics, and assembles the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``   the problem (sizes, loss, law);
* ``bench/traffic/<traffic>.json``  the mix's parameters; its ``kind``
  names the general driver ``bench/drivers/<kind>.py`` that reads them;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the profiler's TPU trace mode and Python tracer level for --trace 1 runs
TRACE_OPTIONS = {"tpu_trace_mode": "TRACE_COMPUTE", "python_tracer_level": 0}


class HarnessError(RuntimeError):
    """A cell, file or device the benchmark cannot run with."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bench: dict, name: str, bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` with its configuration and traffic files."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
    wl = by_name[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    entry = cfgs[wl["config"]]
    config = load_json(os.path.join(os.path.dirname(bench_dir),
                                    entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     wl["traffic"] + ".json"))
    return Cell(
        workload=wl, config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or not os.path.exists(path):
        raise HarnessError(f"missing file {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, bench_dir: str = BENCH):
    return _load_module(os.path.join(bench_dir, "drivers", kind + ".py"),
                        "bench_driver_" + kind.replace(".", "_"))


def load_reader(metric: str, bench_dir: str = BENCH):
    """The ``read(rec)`` function of a per-layer metric's own file."""
    mod = _load_module(os.path.join(bench_dir, "metrics", metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def load_peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["kinds"]:
        raise HarnessError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(have {sorted(table['kinds'])})")
    return table["kinds"][device_kind]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent-cache hits, from its own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                self.events += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.events, self.cache_hits


class Spans:
    """The harness's own host spans around calls into each layer: kept in
    memory as (name, start, end) on ``time.perf_counter`` and, while a
    trace runs, written into the profiler's trace as ``TraceAnnotation``s
    so that idle gaps on the device can be labelled."""

    def __init__(self):
        self.records: list = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))


@dataclass
class Context:
    """What a driver gets: the cell, the run's arguments and devices."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float
    clock: CompileClock
    trace_dir: str = ""
    control: str | None = None  # a lower-precision control (bench/tools)
    log: object = sys.stderr
    spans: Spans = field(default_factory=Spans)
    _traced: object = None
    _trace_start: float = math.inf
    _trace_end: float = math.inf

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def start_trace(self, seconds: float, at: float | None = None) -> None:
        """Trace (``--trace 1`` only) the slice of ``seconds`` that starts
        at ``at`` on ``time.perf_counter`` (now by default; a later start
        is taken by ``trace_poll``): a trace of per-op device events fills
        the profiler's buffer within seconds on a solve
        (``trace_reduce.EVENT_CAP``).  The harness's spans go into the
        trace, and ``bench.traced`` marks the traced slice."""
        if not self.trace:
            return
        self._trace_start = time.perf_counter() if at is None else at
        self._trace_end = self._trace_start + seconds
        self.trace_poll()

    def _begin_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = TRACE_OPTIONS["python_tracer_level"]
        opts.advanced_configuration = {
            "tpu_trace_mode": TRACE_OPTIONS["tpu_trace_mode"]}
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.spans.annotate = True
        self._traced = jax.profiler.TraceAnnotation("bench.traced")
        self._traced.__enter__()

    def trace_poll(self, pending=None) -> None:
        """Start the profiler once the traced slice begins and stop it
        once it has passed; while either is due, wait for ``pending`` (a
        device array) by polling, so that both come on time even when
        the host would block."""
        now = time.perf_counter
        while self._trace_end < math.inf:
            if self._traced is None and now() >= self._trace_start:
                self._begin_trace()
            if self._traced is not None and now() >= self._trace_end:
                self._end_trace()
                break
            if pending is None or pending.is_ready():
                break
            time.sleep(5e-4)

    def _end_trace(self) -> None:
        import jax

        self._trace_end = math.inf
        if self._traced is None:
            return
        self._traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._traced = None
        self.spans.annotate = False

    def stop_trace(self) -> dict | None:
        """Stop the profiler if it still runs, and reduce its trace over
        the traced slice; None when not tracing or when the trace holds
        no device ops."""
        if not self.trace:
            return None
        from bench import trace_reduce

        self._end_trace()
        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            return None
        return trace_reduce.reduce(trace_reduce.extract(path),
                                   window_span="bench.traced")


@dataclass
class Check:
    """One number compared for ``correct``, with its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back: end-to-end values, the run record the
    per-layer readers read, and the checks that decide ``correct``."""

    end_to_end: dict
    rec: dict
    checks: list
    attempted: int
    failed: int
    correct_extra: bool = True  # every answer arrived
    memory_peak_bytes: int = 0


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (a value that occurred; infinite
    entries, for requests that never scored, sort last)."""
    vals = sorted(values)
    if not vals:
        return math.nan
    i = max(math.ceil(q * len(vals)) - 1, 0)
    return float(vals[i])


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def span_totals(spans: Spans, prefix: str) -> dict:
    """Seconds spent in each span whose name starts with ``prefix``."""
    out: dict = {}
    for name, t0, t1 in spans.records:
        if name.startswith(prefix):
            out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def per_layer_values(cell: Cell, rec: dict, bench_dir: str = BENCH):
    """Each applicable per-layer metric read by its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"], bench_dir)(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_values(cell: Cell, values: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise HarnessError(f"driver reported no {m['name']}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def result_line(cell: Cell, outcome: Outcome, *, trace: bool, device: dict,
                breakdown: dict | None) -> dict:
    """The result object; ``checks`` comes last, as the contract asks."""
    correct = (outcome.correct_extra and bool(outcome.checks)
               and all(c.ok for c in outcome.checks))
    metrics = (per_layer_values(cell, outcome.rec) if trace
               else end_to_end_values(cell, outcome.end_to_end))
    line = {"correct": correct, "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # a non-finite reading (a diverged solve) is written as text, so that
    # the line stays JSON that any parser reads
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else repr(c.value), "limit": c.limit}
                      for c in outcome.checks}
    return line
