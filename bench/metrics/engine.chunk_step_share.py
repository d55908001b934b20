"""Share of the window's steps that dispatched the chunk scan (some slot
still prefilling), in %. Each such step stalls every decoding stream for
the whole scan."""


def read(r):
    return 100.0 * r["chunk_steps"] / r["steps"] if r["steps"] else None
