"""merge_us: device microseconds of one round merge: the mean
device-busy of the complete ``passcode.merge`` runs of the traced slice
(the round's full-width Δw, its psum over ``data`` and the fold into w).
Moves solve_s."""

from bench import scopes


def read(rec):
    run_s = scopes.mean_run_s(rec, "passcode.merge")
    return None if run_s is None else run_s * 1e6
