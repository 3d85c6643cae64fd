"""Seconds the traced job spent planning and bucketing its partition on the
host: the program's ``partition.plan`` and ``partition.build_buckets``
spans."""

NAMES = ("partition.plan", "partition.build_buckets")


def read(r):
    spans = [s for s in r.program_spans if s["name"] in NAMES]
    if not spans:
        return None
    return sum(s["dur_s"] for s in spans)
