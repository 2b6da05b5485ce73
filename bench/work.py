"""The work one engine call needs, computed from shapes.

Source: the ELL dual coordinate update of the paper (Hsieh, Yu, Dhillon,
ICML 2015, Algorithm 1 with the sparse row x_i): per update the row's
ids and values are read, k entries of w are gathered for wᵀx_i, α_i and
‖x_i‖² are read, α_i is written, and k entries of w are scatter-added.
The count uses the row's TRUE nonzeros k, not the padded width the
program stores, so whatever implements the update is read against the
same work, and padding shows as lost share.  Bytes are float32 and
int32 (4 bytes each); a scatter-add reads and writes its entries.
"""

from __future__ import annotations

WORD = 4  # bytes of one float32 or int32


def update_bytes(k: int) -> int:
    """HBM bytes one dual coordinate update needs at k true nonzeros:
    k ids + k values + k gathered w entries + k scatter-added w entries
    (read and write) + α_i read and write + ‖x_i‖² read."""
    k = int(k)
    return WORD * (k + k + k + 2 * k + 2 + 1)


def update_flops(k: int) -> int:
    """Operations of one update: the k-term dot (2k), the projected step
    (a handful, counted as 4), and the k-term axpy (2k)."""
    return 4 * int(k) + 4


def update_seconds_min(k: int, peak: dict) -> tuple:
    """The least time the chip could take for one update, and which
    bound sets it ("hbm" or "flops")."""
    t_mem = update_bytes(k) / float(peak["hbm_bytes_per_s"])
    t_ops = update_flops(k) / float(peak["flops_per_s"])
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")
