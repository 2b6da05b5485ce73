"""gap_ms: milliseconds of the on-device gap evaluation per epoch: a
one-epoch dispatch with record=True less one with record=False, from
the device's busy time inside each (host clock where the trace lacks
them).  Moves solve_s."""


def read(rec):
    return rec.get("gap_ms")
