"""update_device_us: device microseconds of the block engine per dual
coordinate update: the mean device-busy of the complete
``passcode.update`` runs of the traced slice (one per block round, the
row loop of one block) over the block size.  Moves solve_s."""

from bench import scopes


def read(rec):
    run_s = scopes.mean_run_s(rec, "passcode.update")
    return None if run_s is None else run_s / scopes.block_size(rec) * 1e6
