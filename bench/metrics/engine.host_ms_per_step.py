"""Host time inside ``ServingEngine.step()``, summed over the window's steps
and divided by them, in ms: the engine's host work per step (admission,
planning, dispatch, tier accounting), without the readback's wait."""


def read(r):
    return r["step_host_s"] / r["steps"] * 1e3 if r["steps"] else None
