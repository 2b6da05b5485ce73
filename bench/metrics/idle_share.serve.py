"""idle_share.serve: % of the traced serving window in which no
operation ran on the device.  Moves score_p95_ms."""


def read(rec):
    tr = rec.get("trace")
    if not tr or rec.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
