"""pad_share: the share of the slots a pass over every row walks that
hold no nonzero, 1 - nnz / slots_walked, from the program's layout
counters (set once at prepare_solver; each row is walked to its own
length rounded up to the walk's group of 16).  Moves solve_s."""


def read(rec):
    nnz, slots = rec.get("nnz"), rec.get("slots_walked")
    if not nnz or not slots:
        return None
    return 1.0 - nnz / slots
