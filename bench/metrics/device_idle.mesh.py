"""Share of the traced window (build and job) in which no op ran on a
device, averaged over the mesh's devices."""


def read(r):
    if r.trace is None:
        return None
    idle = r.trace.idle_share()
    return None if idle is None else 100.0 * idle
