"""The traced job's top program span and the counters the program puts on
it (``single.find_seeds`` or ``mesh.find_seeds``: ``build_sweeps``,
``cascade_sweeps``, ``rebuild_sweeps``, ``compiles``)."""
from __future__ import annotations

from typing import Optional

NAMES = ("single.find_seeds", "mesh.find_seeds")


def counter(readings, key: str) -> Optional[float]:
    """The job span's ``key``, or None where the program records no such
    span or attribute."""
    for span in readings.program_spans:
        attrs = span.get("attrs") or {}
        if span["name"] in NAMES and key in attrs:
            return float(attrs[key])
    return None
