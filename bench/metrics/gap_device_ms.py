"""gap_device_ms: device milliseconds of one recorded epoch's gap: the
mean device-busy of the complete ``passcode.gap`` runs of the traced
slice (the cond-gated gap and ε evaluation and the writes into the
record buffers).  Moves solve_s."""

from bench import scopes


def read(rec):
    run_s = scopes.mean_run_s(rec, "passcode.gap")
    return None if run_s is None else run_s * 1e3
