"""prepare_s: seconds of the host set-up layer, `prepare_solver`
(host padding and placement of the dataset on the mesh), by the
harness's host clock around the call.  Moves setup_s."""


def read(rec):
    return rec.get("prepare_s")
