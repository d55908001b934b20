"""The segmented tiered-gather kernel's share of its roofline, in %.

The least time the traced calls could take is the bytes they need (from
``bench/costs/tiered_gather``: each gather's source row from the tier that
holds it, its float32 output row, its index words) over the chip's HBM
bandwidth; the bandwidth bound applies, since a far row's dequantizing
multiply is one operation per four bytes moved. That time over the
kernel's device time in the trace is the share."""
from bench import trace
from bench.costs import tiered_gather

MODULE = "_tiered_lookup_segments"  # the jitted call around the kernel
KERNEL = "custom-call"  # the pallas kernel inside it


def read(r):
    if not r["trace"] or not r["lookups"]:
        return None
    t = sum(trace.op_times(r["trace"], MODULE, KERNEL))
    if t <= 0:
        return None
    d = tiered_gather.row_dim(r["model"])
    need = sum(tiered_gather.bytes_needed(n, f, d) for n, f in r["lookups"])
    return 100.0 * need / r["peaks"]["hbm_bytes_per_s"] / t
