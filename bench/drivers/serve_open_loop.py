"""Window driver for open-loop scoring traffic against ``ServeEngine``.

Set-up: the configuration's test rows from the seed (``bench/gen.py``),
a (d + 1,) primal drawn from the seed as the published snapshot (a
scoring dispatch costs the same for any w, so no solve is needed), the
engine with ``max_batch`` and ``k_max`` = the rows' width, its scoring
dispatch compiled, and its loop started.

Window: one generator thread sends the rows, in an order drawn from the
seed, on an open-loop schedule at ``rate_rps`` for the window's length:
the inter-arrival gaps are the ``N = rate·seconds`` quantiles of an
exponential law (Poisson arrivals at that rate), shuffled by the seed,
so every seed offers the same load in another order.  Each request is
timed from when it was due to when the engine resolved it (its
``ScoreOutcome`` stamp); a shed or unanswered request counts as later
than any answer.  The generator records how late it sent each request.
After the window the driver waits, up to ``drain_s``, for every request
to resolve.

Parameters (``bench/traffic/<mix>.json``): ``rate_rps``, ``max_batch``,
``deadline_ms``, ``latency_limit_ms`` (the knee's criterion, recorded
for the sweep), ``drain_s``, ``trace_seconds`` (the traced slice of a
``--trace 1`` window), ``limits``.
"""

from __future__ import annotations

import collections
import math
import threading
import time

import numpy as np

from bench import gen, reference
from bench.harness import (
    Check,
    Outcome,
    nearest_rank,
    peak_bytes,
    span_totals,
)
from repro.serve import ScoreOutcome


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times (seconds from the window's start) of an open-loop
    Poisson schedule: the exponential quantiles of N = rate·seconds
    gaps, in an order drawn from ``seed``."""
    n = max(int(round(rate * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng(gen.seed_words(seed, 11))
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])


class Sender(threading.Thread):
    """The load generator: submits request i at ``t0 + offsets[i]``,
    whether or not earlier ones have resolved, and records when it
    actually sent each one.  While it waits for the next due time it
    reads the resolved requests at the head of its in-flight queue and
    drops their tickets: tickets held to the end of the window would keep
    some 10^5 live objects for each full garbage collection to walk, and
    such a pass stalls the engine's thread for over 100 ms."""

    def __init__(self, engine, cols, vals, order, offsets, deadline_s):
        super().__init__(name="bench-sender", daemon=True)
        self.engine, self.cols, self.vals = engine, cols, vals
        self.order, self.offsets = order, offsets
        self.deadline_s = deadline_s
        n = len(offsets)
        self.sent = np.zeros(n)
        self.engine_s = np.full(n, math.inf)  # enqueue to resolution
        self.scores = np.full(n, math.nan)
        self.scored = np.zeros(n, bool)
        self.resolved = np.zeros(n, bool)
        self.in_flight: collections.deque = collections.deque()
        self.t0 = 0.0
        self.error = None

    def run(self):
        try:
            self._run()
        except Exception as e:  # reported by the driver after join
            self.error = e

    def harvest(self, wait_until: float | None = None) -> None:
        """Record the resolved requests at the head of the in-flight
        queue; with ``wait_until``, wait for each until then."""
        q = self.in_flight
        while q:
            i, tk = q[0]
            if not tk.done():
                left = (0.0 if wait_until is None
                        else wait_until - time.monotonic())
                if left <= 0:
                    return
                try:
                    tk.result(timeout=left)
                except TimeoutError:
                    return
            q.popleft()
            out = tk.result(timeout=0)
            self.resolved[i] = True
            if isinstance(out, ScoreOutcome):
                self.scored[i] = True
                self.scores[i] = out.score
                self.engine_s[i] = out.latency_s

    def _run(self):
        mono = time.monotonic
        sub = self.engine.submit
        n = len(self.offsets)
        i = 0
        while i < n:
            t = mono()
            due = self.t0 + self.offsets[i]
            if due > t:
                self.harvest()
                ahead = due - mono()
                if ahead > 5e-4:
                    time.sleep(ahead - 2e-4)
                continue
            while i < n and self.t0 + self.offsets[i] <= t:
                r = self.order[i]
                self.sent[i] = mono()
                self.in_flight.append((i, sub(
                    cols=self.cols[r], vals=self.vals[r],
                    deadline_s=self.deadline_s)))
                i += 1


def run(ctx) -> Outcome:
    import jax

    from repro.serve import ServeEngine, SnapshotStore, make_snapshot

    cfg, mix, spans = ctx.config, ctx.traffic, ctx.spans
    d = int(cfg["d"])
    rate = float(mix["rate_rps"])
    deadline = float(mix["deadline_ms"]) * 1e-3
    now = time.perf_counter

    with spans("bench.setup.data"):
        test = gen.make_problem(cfg, ctx.seed, train=False)[1]
        cols = np.asarray(test.indices)
        vals = np.asarray(test.values)
        w = np.asarray(jax.random.normal(gen.key_from_seed(ctx.seed, 3),
                                         (d,), jax.numpy.float32))
    with spans("bench.setup.engine"):
        engine = ServeEngine(SnapshotStore(make_snapshot(w, 1)),
                             k_max=cols.shape[1],
                             max_batch=int(mix["max_batch"]))
        if ctx.control == "bf16":
            engine._score = jax.jit(_bf16_score)
        # compile the fixed-shape scoring dispatch before the window
        for r in range(int(mix["max_batch"])):
            engine.submit(cols=cols[r], vals=vals[r], deadline_s=600.0)
        while engine.step():
            pass
    offsets = arrival_offsets(rate, ctx.seconds, ctx.seed)
    rng = np.random.default_rng(gen.seed_words(ctx.seed, 12))
    order = rng.permutation(np.resize(rng.permutation(len(cols)),
                                      len(offsets)))
    sender = Sender(engine, cols, vals, order, offsets, deadline)
    engine.start()
    setup_s = now() - ctx.t_process
    compile_s, compile_events, _ = ctx.clock.read()
    m0 = engine.metrics.snapshot()

    ctx.start_trace(float(mix["trace_seconds"]))
    with spans("bench.window"):
        sender.t0 = time.monotonic() + 1e-3
        sender.start()
        while sender.is_alive():
            sender.join(timeout=0.05)
            ctx.trace_poll()
        with spans("bench.drain"):
            sender.harvest(wait_until=time.monotonic()
                           + float(mix["drain_s"]))
    t_stop = time.monotonic()
    m1 = engine.metrics.snapshot()
    trace = ctx.stop_trace()
    engine.stop()
    window_compiles = ctx.clock.read()[1] - compile_events
    mem = peak_bytes(ctx.devices[:1])
    if sender.error is not None:
        raise sender.error

    n = len(offsets)
    due = sender.t0 + offsets
    scored, scores = sender.scored, sender.scores
    lat = np.where(scored, sender.sent - due + sender.engine_s, math.inf)
    unresolved = int(n - sender.resolved.sum())
    lag = sender.sent - due
    good = int(np.sum(scored & (lat <= deadline)))
    # a missing request is later than any answer; where the rank falls
    # on one, the p95 reads the longest any request could have waited:
    # from the window's start to the end of the drain
    p95 = min(nearest_rank(lat, 0.95), t_stop - sender.t0)
    served = m1["served"] - m0["served"]
    batches = m1["batches"] - m0["batches"]
    half = n // 2
    rec = {"kind": "serve", "compile_s": compile_s,
           "p95_halves_ms": [nearest_rank(lat[:half], 0.95) * 1e3,
                             nearest_rank(lat[half:], 0.95) * 1e3],
           "window_compiles": window_compiles,
           "rows_per_dispatch": served / batches if batches else None,
           "sender_lag_p95_ms": nearest_rank(lag, 0.95) * 1e3,
           "trace": trace}
    print(f"bench: {n} requests at {rate} req/s, {int(scored.sum())} "
          f"scored, {n - int(scored.sum()) - unresolved} shed, "
          f"{unresolved} unresolved; p50 "
          f"{nearest_rank(lat, 0.5) * 1e3:.3f} ms, p95 {p95 * 1e3:.3f} ms, "
          f"p99 {nearest_rank(lat, 0.99) * 1e3:.3f} ms (p95 by half "
          f"{rec['p95_halves_ms']}); sender lag p95 "
          f"{rec['sender_lag_p95_ms']:.3f} ms; {batches} dispatches; "
          f"{window_compiles} compiles in the window; set-up spans "
          f"{ {k: round(v, 3) for k, v in span_totals(spans, 'bench.setup').items()} }",
          file=ctx.log)

    err = reference.score_errors(cols[order[scored]], vals[order[scored]],
                                 w, scores[scored])
    checks = [Check("score", float(err.max()) if err.size else 0.0,
                    float(mix["limits"]["score"]))]
    e2e = {"setup_s": setup_s, "score_p95_ms": p95 * 1e3,
           "score_goodput_rps": good / ctx.seconds}
    return Outcome(end_to_end=e2e, rec=rec, checks=checks, attempted=n,
                   failed=n - int(scored.sum()),
                   correct_extra=unresolved == 0 and bool(scored.any()),
                   memory_peak_bytes=mem)


def _bf16_score(w_pad, cols, vals):
    """The control: the reference's sparse dot put in the engine's place
    with its products in bfloat16 (the nearest precision below the
    stated float32), summed in float32."""
    import jax.numpy as jnp

    w2 = jnp.asarray(w_pad, jnp.bfloat16)[None]
    prod = w2[:, cols] * jnp.asarray(vals, jnp.bfloat16)[None]
    return jnp.sum(prod.astype(jnp.float32), axis=-1)
