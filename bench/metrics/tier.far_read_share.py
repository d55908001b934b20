"""Far-tier hits over all tier-plane hits, in %, drained from the device
counter plane across the window."""


def read(r):
    n = r["near_hits"] + r["far_hits"]
    return 100.0 * r["far_hits"] / n if n else None
