"""sender_lag_p95_ms: 95th percentile of how late the load generator
sent a request (send time - due time), by the harness's host clock.
Moves score_p95_ms."""


def read(rec):
    return rec.get("sender_lag_p95_ms")
