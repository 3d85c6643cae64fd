"""Seconds the traced job spent preparing its inputs on the host: the
program's ``entry.normalize_inputs`` (destination sort, x sort) and
``entry.edge_operands`` (model hashing and upload, synced) spans."""

NAMES = ("entry.normalize_inputs", "entry.edge_operands")


def read(r):
    spans = [s for s in r.program_spans if s["name"] in NAMES]
    if not spans:
        return None
    return sum(s["dur_s"] for s in spans)
