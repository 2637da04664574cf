"""The least HBM traffic of a shuffle, counted from the plan's shapes.

A shuffle delivers each of its ``n`` items to a destination node.  Whatever
implements it, it has to read every item once, together with its destination
(``dest_bytes``, an int32 node id: 4 bytes), and write every item once at its
place in the output.  Items of ``item_bytes`` bytes therefore cost at least

    n * (item_bytes + dest_bytes)      read
  + n * item_bytes                     written

bytes of HBM traffic per shuffle, and a plan whose kernel route carries
``shuffles`` shuffles of ``n`` items each costs ``shuffles`` times that.
The least time is those bytes over the chip's HBM bandwidth: a shuffle does
no arithmetic to speak of, so the memory bound is the roofline.  The count
depends on (n, item width, number of kernel-routed shuffles) only, not on
the node count V nor on the slots of the mailboxes the program happens to
allocate, so it reads the same for any implementation of the route.
"""
from __future__ import annotations

DEST_BYTES = 4


def shuffle_bytes(n_items: int, item_bytes: int, shuffles: int) -> int:
    """Least HBM bytes that ``shuffles`` shuffles of ``n_items`` items of
    ``item_bytes`` bytes each must move."""
    return shuffles * n_items * (2 * item_bytes + DEST_BYTES)


def least_seconds(n_bytes: float, hbm_bytes_per_s: float) -> float:
    """Least time to move ``n_bytes`` at the HBM peak."""
    return n_bytes / hbm_bytes_per_s
