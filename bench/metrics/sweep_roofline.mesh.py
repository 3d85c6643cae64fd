"""Share of the mesh's HBM roofline in the traced build: the least bytes of
the reference's sweeps at the peak bandwidth of all the traced devices
together, over the device-busy time inside the ``bench.build`` span,
averaged over the devices."""
from bench import bytecount


def read(r):
    if r.trace is None or r.peaks is None or r.ref_build_sweeps is None:
        return None
    span = r.trace.data.annotation("bench.build")
    chips = len(r.trace.data.devices)
    if span is None or chips == 0:
        return None
    busy = r.trace.busy_s(*span)
    if busy <= 0:
        return None
    least = bytecount.fixpoint_least_bytes(r.n, r.m, r.registers,
                                           r.ref_build_sweeps)
    return 100.0 * least / (chips * r.peaks.hbm_bw) / busy
