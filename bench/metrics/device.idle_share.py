"""Share of the traced window in which no operation ran on the device, in %
(1 - union of the busy intervals over the window), averaged over chips."""
from bench import trace


def read(r):
    tr = r["trace"]
    if not tr or not tr.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / tr.window_s)
