"""perm_ms: device milliseconds of one epoch's update-order draw: the
mean device-busy of the complete ``passcode.perm`` runs of the traced
slice (permutation, stable argsort, cycling).  Moves solve_s."""

from bench import scopes


def read(rec):
    run_s = scopes.mean_run_s(rec, "passcode.perm")
    return None if run_s is None else run_s * 1e3
