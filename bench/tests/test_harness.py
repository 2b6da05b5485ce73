"""The harness's own arithmetic and files, on the CPU."""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import gen, harness, reference, trace_reduce, work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


# ------------------------------------------------------ trace reduction ----

def _trace():
    """Two devices over a 100 ns window: device 0 runs a fusion in
    [10, 30], an all-reduce in [25, 40] and a fusion in [70, 90];
    device 1 is busy over [0, 50].  The host spans label the gaps."""
    return {
        "devices": {
            0: [("fusion.1", 10, 30), ("all-reduce.3", 25, 40),
                ("fusion.1", 70, 90)],
            1: [("fusion.2", 0, 50)],
        },
        "spans": [("bench.window", 0, 100), ("bench.epoch", 5, 45),
                  ("bench.gap_read", 45, 75)],
        "op_line": "XLA Ops",
    }


def test_trace_busy_union_and_idle_share():
    tr = _trace()
    assert trace_reduce.busy(tr["devices"][0], (0, 100)) == 50
    out = trace_reduce.reduce(tr)
    assert out["window_s"] == pytest.approx(100e-9)
    # mean over the devices of the busy union: (50 + 50) / 2
    assert out["busy_s"] == pytest.approx(50e-9)


def test_trace_allreduce_share():
    out = trace_reduce.reduce(_trace())
    assert out["allreduce_share"] == pytest.approx(0.15)


def test_trace_idle_gaps_labelled_by_host_span():
    out = trace_reduce.reduce(_trace())
    # gaps of device 0: [0, 10] (epoch starts at 5: midpoint 5 is in
    # the epoch), [40, 70] (midpoint 55: gap read), [90, 100] (window)
    assert out["idle_gaps"] == [
        ["bench.gap_read", pytest.approx(30e-9)],
        ["bench.epoch", pytest.approx(10e-9)],
        ["bench.window", pytest.approx(10e-9)],
    ]
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(40e-9)]


def test_trace_at_the_event_cap_is_reduced_over_what_it_covers():
    tr = _trace()
    tr["events"] = {0: trace_reduce.EVENT_CAP, 1: 10}
    out = trace_reduce.reduce(tr)
    # the profiler stopped at 50 (device 1's last op, the earlier end)
    assert out["truncated"] and out["window_s"] == pytest.approx(50e-9)
    assert out["busy_s"] == pytest.approx((30e-9 + 50e-9) / 2)


def test_trace_without_device_ops_reads_nothing():
    tr = _trace()
    tr["devices"] = {}
    assert trace_reduce.reduce(tr) is None


def test_trace_extract_reads_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_reduce.extract(trace_reduce.find_xplane(str(tmp_path)))
    names = [s[0] for s in tr["spans"]]
    assert names == ["bench.window"]
    s, e = trace_reduce.span_window(tr["spans"], "bench.window")
    assert e > s


# ------------------------------------------------------- interpolation ----

def _driver(kind):
    return harness.load_driver(kind)


def test_crossing_interpolates_on_log_gap():
    crossing = _driver("solve").crossing
    # gaps 100 → 10 → 1 over epochs at 0, 2, 4 s: 10 is reached exactly
    # at epoch 1; 3.16 (10^0.5) half-way through epoch 2
    t, e = crossing([0.0, 2.0, 4.0], [100.0, 10.0, 1.0], 10.0)
    assert (t, e) == (pytest.approx(2.0), pytest.approx(1.0))
    t, e = crossing([0.0, 2.0, 4.0], [100.0, 10.0, 1.0], 10 ** 0.5)
    assert (t, e) == (pytest.approx(3.0), pytest.approx(1.5))


def test_crossing_never_reached_and_zero_gap():
    crossing = _driver("solve").crossing
    assert crossing([0.0, 1.0], [100.0, 50.0], 10.0) is None
    # a gap that falls to zero: no log to interpolate, the whole epoch
    t, e = crossing([0.0, 1.0, 3.0], [100.0, 50.0, 0.0], 10.0)
    assert (t, e) == (pytest.approx(3.0), pytest.approx(2.0))


def test_zero_start_gap_is_closed_form():
    cfg = harness.load_json(os.path.join(harness.ROOT, "bench", "configs",
                                         "news20.json"))
    assert reference.zero_gap(cfg) == 2.0 * 16000
    ids = np.array([[0, 1], [1, 2]])
    vals = np.array([[0.6, 0.8], [1.0, 0.0]])
    loss = reference.Hinge(2.0)
    assert reference.duality_gap(ids, vals, np.zeros(2), 3, loss) == 4.0


# ------------------------------------------------------------- work ----

@pytest.mark.parametrize("config,k,per_update,per_epoch", [
    ("rcv1", 73, 1472, 1472 * 677_399),
    ("news20", 455, 9112, 9112 * 16_000),
])
def test_work_bytes(config, k, per_update, per_epoch):
    cfg = harness.load_json(os.path.join(harness.ROOT, "bench", "configs",
                                         config + ".json"))
    assert cfg["nnz_per_row"] == k
    assert work.update_bytes(k) == per_update
    assert work.update_bytes(k) * cfg["n_train"] == per_epoch
    t, bound = work.update_seconds_min(k, {"hbm_bytes_per_s": 819e9,
                                           "flops_per_s": 197e12})
    assert bound == "hbm" and t == pytest.approx(per_update / 819e9)


# --------------------------------------------------------- generator ----

LAW = dict(zipf_exponent=0.9, label_noise=0.02, margin=0.5)


def _split(seed, n=3000, d=800, k=24):
    cfg = dict(n_train=n, n_test=10, d=d, nnz_per_row=k, assumed=LAW)
    tr, _ = gen.make_problem(cfg, seed, test=False)
    return np.asarray(tr.indices), np.asarray(tr.values)


def test_generator_rows_hold_k_distinct_ids():
    ids, vals = _split(2**40 + 3)
    assert ids.shape == (3000, 24) and ids.dtype == np.int32
    assert all(len(set(r)) == 24 for r in ids)
    assert ids.min() >= 0 and ids.max() < 800


def test_generator_rows_unit_norm_and_label_folded():
    ids, vals = _split(9)
    np.testing.assert_allclose((vals.astype(np.float64) ** 2).sum(1), 1.0,
                               atol=1e-5)
    # folding flips whole rows: both signs of the first value occur
    assert 0.3 < float(np.mean(vals[:, 0] > 0)) < 0.7


def test_generator_is_seed_deterministic():
    a = _split(2**33 + 17)
    b = _split(2**33 + 17)
    c = _split(2**33 + 18)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_generator_seeds_beyond_32_bits_differ():
    assert gen.seed_words(2**32 + 1) != gen.seed_words(1)


def test_generator_ids_follow_the_zipf_law():
    ids, _ = _split(4, n=6000)
    p = 1.0 / np.arange(1, 801) ** 0.9
    p /= p.sum()
    freq = np.bincount(ids.ravel(), minlength=800) / ids.size
    # the first id of a row is an i.i.d. Zipf draw
    first = np.bincount(ids[:, 0], minlength=800) / len(ids)
    assert abs(first[0] - p[0]) < 4 * math.sqrt(p[0] / len(ids))
    # without replacement: the head id is in nearly every row, once
    assert 0.9 < freq[0] * 24 <= 1.0


def test_zipf_tables_are_monotone_and_end_at_the_top():
    (hi, lo, first, last), n_iter = gen.zipf_tables(1000, 0.9)
    v = hi.astype(np.uint64) << np.uint64(32) | lo.astype(np.uint64)
    assert np.all(np.diff(v) > 0) and v[-1] == np.uint64(2**64 - 1)
    assert np.all(first <= last) and np.all(np.diff(first) >= 0)
    assert first[0] == 0 and last[-1] == 999 and 1 <= n_iter <= 11


@pytest.mark.parametrize("d", [47236, 1355191])
def test_zipf_draws_equal_a_search_over_all_ids(d):
    """The bucketed search lands where a plain search of the uniform over
    the whole cumulative table does, draw for draw, at both widths."""
    import jax

    tables, n_iter = gen.zipf_tables(d, 0.9)
    key = gen.key_from_seed(2**35 + 7, 5)
    got = np.asarray(gen._zipf_draws(key, tuple(map(jax.numpy.asarray,
                                                    tables)),
                                     (512, 64), n_iter))
    kh, kl = jax.random.split(key)
    u = (np.asarray(jax.random.bits(kh, (512, 64), np.uint32))
         .astype(np.uint64) << np.uint64(32)) | np.asarray(
        jax.random.bits(kl, (512, 64), np.uint32)).astype(np.uint64)
    want = np.minimum(np.searchsorted(gen._cdf_u64(d, 0.9), u,
                                      side="right"), d - 1)
    assert np.array_equal(got, want)
    assert n_iter < int(np.ceil(np.log2(d + 1))) + 1


# ---------------------------------------------------- chip and files ----

def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.HarnessError):
        harness.load_peaks("TPU v9 imaginary")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_files_of_its_own(cell):
    c = harness.resolve_cell(BENCH, cell)
    driver = harness.load_driver(c.traffic["kind"])
    assert callable(driver.run)
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_names_and_units_use_allowed_characters(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    for w in m.get("workloads", []):
        assert w in CELLS


def test_benchmark_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25
    # a full check of 24 cells fits its time
    r = BENCH["run_seconds"]
    assert 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_per_layer_metrics_name_one_layer_and_one_end_to_end_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        cells = set(m.get("workloads", CELLS))
        assert cells <= set(moved.get("workloads", CELLS))
        assert m["layer"] and "\n" not in m["layer"]


def test_nearest_rank_counts_missing_requests_as_late():
    assert harness.nearest_rank([1.0, 2.0, math.inf, 3.0], 0.5) == 2.0
    assert harness.nearest_rank([1.0] * 19 + [math.inf], 0.95) == 1.0
    assert harness.nearest_rank([1.0] * 18 + [math.inf] * 2,
                                0.95) == math.inf


def test_traced_slice_starts_and_stops_when_set(tmp_path):
    """A slice set to start later is begun by the first poll past its
    start, and ended by the first poll past its end."""
    import time

    cell = harness.resolve_cell(BENCH, "news20.solve")
    ctx = harness.Context(cell=cell, seed=1, seconds=1.0, trace=True,
                          devices=[], t_process=0.0, clock=None,
                          trace_dir=str(tmp_path / "trace"))
    ctx.start_trace(0.2, at=time.perf_counter() + 0.1)
    assert ctx._traced is None
    time.sleep(0.12)
    ctx.trace_poll()
    assert ctx._traced is not None
    time.sleep(0.22)
    ctx.trace_poll()
    assert ctx._traced is None
    assert trace_reduce.find_xplane(str(tmp_path / "trace")) is not None


def test_sweep_knee_is_the_last_rate_that_keeps_up():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_sweep", os.path.join(harness.BENCH, "tools", "sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)

    def reading(rate, p95, halves, shed):
        return {"rate_rps": rate, "requests": 10000, "shed": shed,
                "p95_ms": p95, "p95_halves_ms": halves}

    ok = reading(1000, 5.0, [5.0, 5.0], 3)
    assert sweep.keeps_up(ok, 20.0)
    assert not sweep.keeps_up(reading(1, 25.0, [25.0, 25.0], 0), 20.0)
    assert not sweep.keeps_up(reading(1, 9.0, [5.0, 9.0], 0), 20.0)
    assert not sweep.keeps_up(reading(1, 5.0, [5.0, 5.0], 11), 20.0)
    readings = [ok, reading(2000, 6.0, [6.0, 6.5], 0),
                reading(3000, 9.0, [5.0, 12.0], 40),
                reading(4000, 6.0, [6.0, 6.0], 0)]
    # 4,000 reads well but lies past the first rate that fell behind
    assert sweep.knee(readings, 20.0) == 2000
    assert sweep.knee(readings[2:3], 20.0) is None
