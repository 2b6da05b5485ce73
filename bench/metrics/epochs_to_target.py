"""epochs_to_target: epochs of the solver pipeline to the target gap,
interpolated on log-gap as solve_s is, averaged over the window's
finished solves.  Moves solve_s."""


def read(rec):
    return rec.get("epochs_to_target")
