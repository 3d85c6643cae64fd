"""Device-busy milliseconds per sweep in the traced job: the busy time
inside the ``bench.job`` span of the trace, over all the job's sweeps (its
first fixpoint, cascades and rebuilds, as the program counts them)."""
from bench import jobspan

KEYS = ("build_sweeps", "cascade_sweeps", "rebuild_sweeps")


def read(r):
    if r.trace is None:
        return None
    span = r.trace.data.annotation("bench.job")
    counts = [jobspan.counter(r, key) for key in KEYS]
    if span is None or None in counts or sum(counts) <= 0:
        return None
    busy = r.trace.busy_s(*span)
    if busy <= 0:
        return None
    return 1000.0 * busy / sum(counts)
