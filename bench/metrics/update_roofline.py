"""update_roofline: % of its roofline the block engine reaches per dual
coordinate update: bench/work.py's least time for an update at the
epoch's mean TRUE nonzeros per row (nnz / n, from the program's layout
counters; peaks from bench/peaks.json) over the mean device-busy of the
complete ``passcode.update`` runs of the traced slice per update.
Moves solve_s."""

import json
import os

from bench import scopes, work


def read(rec):
    nnz, n, kind = rec.get("nnz"), rec.get("n_rows"), rec.get("device_kind")
    if not nnz or not n or not kind:
        return None
    run_s = scopes.mean_run_s(rec, "passcode.update")
    if not run_s:
        return None
    with open(os.path.join(scopes.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["kinds"]
    if kind not in peaks:
        return None
    t_min, _ = work.update_seconds_min(nnz / n, peaks[kind])
    return 100.0 * t_min / (run_s / scopes.block_size(rec))
