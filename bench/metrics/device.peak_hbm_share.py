"""``peak_bytes_in_use`` of the fullest chip after the window over the HBM
of its device kind (``bench/peaks.json``), in %."""


def read(r):
    return 100.0 * r["peak_bytes"] / r["peaks"]["hbm_bytes"]
