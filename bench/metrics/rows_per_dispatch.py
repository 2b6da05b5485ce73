"""rows_per_dispatch: requests scored per dispatch of the serving host
path (ServeEngine.step), from the engine's own counters (served /
batches) over the window.  Moves score_p95_ms."""


def read(rec):
    return rec.get("rows_per_dispatch")
