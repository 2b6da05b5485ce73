"""update_us: microseconds of the block engine per dual coordinate
update on one device: the record=False one-epoch dispatch over the
updates each device makes in it.  Moves solve_s."""


def read(rec):
    return rec.get("update_us")
