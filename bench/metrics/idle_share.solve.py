"""idle_share.solve: % of the traced solve window in which no operation
ran on the device (1 - busy union / window), averaged over the chips.
Moves solve_s."""


def read(rec):
    tr = rec.get("trace")
    if not tr or rec.get("kind") != "solve":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
