"""Small stand-ins for the benchmark's cells, run on the CPU."""

from __future__ import annotations

import io
import os
import time

from bench import harness

TINY = dict(name="tiny", n_train=6000, n_test=400, d=1500, nnz_per_row=16,
            C=1.0, loss="hinge",
            assumed=dict(zipf_exponent=0.9, label_noise=0.02, margin=0.5))
# PASSCoDe's data=4 rounds add four devices' updates at once; on a
# problem much smaller than this they overshoot and the gap stalls
WIDE = dict(TINY, n_train=40000, d=8000, nnz_per_row=30)


def tiny_cell(workload: str, config=None, **traffic) -> harness.Cell:
    """The cell ``workload`` of BENCHMARK.json on a small configuration,
    with traffic parameters overridden."""
    cell = harness.resolve_cell(harness.load_benchmark(), workload)
    cell.config = dict(config or (WIDE if cell.chips == 4 else TINY))
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def data4_cell(**traffic) -> harness.Cell:
    """``rcv1.solve``'s mix on a data = 4 mesh, the path of a four-chip
    cell that BENCHMARK.json does not list yet, on the WIDE
    configuration."""
    base = harness.resolve_cell(harness.load_benchmark(), "rcv1.solve")
    return harness.Cell(dict(base.workload, name="rcv1.solve.data4",
                             chips=4),
                        dict(WIDE), dict(base.traffic, data=4, **traffic),
                        base.end_to_end, base.per_layer)


def serve_cell(**traffic) -> harness.Cell:
    """rcv1's test rows under the ``serve_poisson`` mix on the TINY
    configuration: the serving cell, which BENCHMARK.json holds out
    until its knee is swept again (PERF.md)."""
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "serve_poisson.json"))
    e2e = [{"name": "setup_s", "unit": "s"},
           {"name": "score_p95_ms", "unit": "ms"},
           {"name": "score_goodput_rps", "unit": "req/s"}]
    return harness.Cell(dict(name="rcv1.serve", config="rcv1",
                             traffic="serve_poisson", chips=1),
                        dict(TINY), dict(mix, **traffic), e2e, [])


def run(cell, *, seed: int = 2**33 + 5, seconds: float = 1.0,
        control=None, trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU devices; its result object."""
    import jax

    from bench.run import run_cell

    return run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                    devices=jax.devices(),
                    clock=harness.CompileClock(),
                    t_process=time.perf_counter(), control=control,
                    log=io.StringIO())
