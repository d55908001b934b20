"""Device time per call of the decode-only executable (``_decode_step``,
one (B, 1) pass), in ms, from the ``XLA Modules`` line of the trace."""
from bench import trace


def read(r):
    t = trace.module_times(r["trace"], "_decode_step") if r["trace"] else []
    return sum(t) / len(t) * 1e3 if t else None
