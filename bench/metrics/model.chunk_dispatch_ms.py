"""Device time per call of the chunk-scan executable (``_chunk_step``, a
scan of single-token passes over every prefill column), in ms, from the
``XLA Modules`` line of the trace."""
from bench import trace


def read(r):
    t = trace.module_times(r["trace"], "_chunk_step") if r["trace"] else []
    return sum(t) / len(t) * 1e3 if t else None
