"""Benchmark driver — one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows.  Sections whose ``main()``
returns row dicts additionally persist them as out/BENCH_<tag>.json AND
mirror the file to the repo root (BENCH_<tag>.json) so the cross-PR
perf trajectory is visible without digging into out/ (currently: the
DCD Pallas kernel section → BENCH_kernel.json, fused vs unfused epoch;
the sparse ELL section → BENCH_sparse.json, dense-vs-ELL epoch + VMEM
frontier; the 2D feature-sharded section → BENCH_feature.json,
1D-vs-2D d-sweep + three-policy VMEM frontier; the multi-epoch pipeline
section → BENCH_pipeline.json, driver-vs-pipeline dispatch overhead +
overlap round; the adaptive self-tuning section → BENCH_adaptive.json,
wall-clock-to-ε of shrinking/adaptive vs the static schedules;
the pod double-async section → BENCH_pod.json, convergence-vs-staleness
sweep + pod-axis mesh overhead; the resilient solver section →
BENCH_resilience.json, checkpoint overhead per segment + recovery
cost/epochs-lost per fault class; the serving engine section →
BENCH_serve.json, p50/p99 latency + sustained QPS, shed rate under
overload, hot-swap pause; the multi-task OvR section →
BENCH_multiclass.json, batched-task-axis vs loop-over-K wall clock
across the K-sweep).
"""

from __future__ import annotations

import json
import os
import sys
import time


# anchored to the repo root (not the process cwd) so the committed
# artifacts are updated no matter where run.py is invoked from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _persist(tag: str, rows) -> None:
    from benchmarks.common import env_info

    env = env_info()
    # every row carries the backend / interpret-vs-compiled stamp so a
    # CPU-interpret semantics number can never be misread as a TPU perf
    # claim once the JSON is detached from the machine that wrote it
    for r in rows:
        r.setdefault("backend", env["backend"])
        r.setdefault("mode", env["mode"])
    out_dir = os.path.join(_ROOT, "out")
    os.makedirs(out_dir, exist_ok=True)
    # out/ is the working artifact; the repo-root mirror is the
    # cross-PR perf record (committed alongside the code it measures)
    for path in (os.path.join(out_dir, f"BENCH_{tag}.json"),
                 os.path.join(_ROOT, f"BENCH_{tag}.json")):
        with open(path, "w") as f:
            json.dump({"env": env, "rows": rows}, f, indent=2)
        print(f"# wrote {os.path.relpath(path)} ({len(rows)} rows)",
              file=sys.stderr)


def main() -> None:
    from repro.runtime import use_compile_cache

    use_compile_cache(_ROOT)
    from benchmarks import (
        bench_accuracy,
        bench_adaptive,
        bench_convergence,
        bench_feature,
        bench_kernel,
        bench_multiclass,
        bench_pipeline,
        bench_pod,
        bench_resilience,
        bench_roofline,
        bench_scaling,
        bench_serve,
        bench_sparse,
        bench_speedup,
    )

    sections = [
        ("Table 1 (scaling)", bench_scaling, None),
        ("Table 2 (w_hat vs w_bar accuracy)", bench_accuracy, None),
        ("Fig 4-6a (convergence)", bench_convergence, None),
        ("Fig 2-6d (speedup)", bench_speedup, None),
        ("DCD Pallas kernel", bench_kernel, "kernel"),
        ("Sparse ELL path", bench_sparse, "sparse"),
        ("2D feature-sharded solver", bench_feature, "feature"),
        ("Multi-epoch pipeline", bench_pipeline, "pipeline"),
        ("Adaptive self-tuning solver", bench_adaptive, "adaptive"),
        ("Pod double-async solver", bench_pod, "pod"),
        ("Resilient solver", bench_resilience, "resilience"),
        ("Online serving engine", bench_serve, "serve"),
        ("Multi-task OvR solver", bench_multiclass, "multiclass"),
        ("Roofline (dry-run artifacts)", bench_roofline, None),
    ]
    print("name,us_per_call,derived")
    for title, mod, tag in sections:
        print(f"# --- {title} ---", file=sys.stderr)
        t0 = time.time()
        rows = mod.main()
        if tag is not None and rows:
            _persist(tag, rows)
        print(f"# {title}: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
