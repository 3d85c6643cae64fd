"""Share of the HBM roofline in the traced build: the least bytes of the
reference's sweeps at peak bandwidth, over the device-busy time inside the
``bench.build`` span of the trace."""
from bench import bytecount


def read(r):
    if r.trace is None or r.peaks is None or r.ref_build_sweeps is None:
        return None
    span = r.trace.data.annotation("bench.build")
    if span is None:
        return None
    busy = r.trace.busy_s(*span)
    if busy <= 0:
        return None
    least = bytecount.fixpoint_least_bytes(r.n, r.m, r.registers,
                                           r.ref_build_sweeps)
    return 100.0 * least / r.peaks.hbm_bw / busy
