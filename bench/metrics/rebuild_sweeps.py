"""Fixpoint sweeps of the traced job's lazy rebuilds, summed over its seed
rounds (the job span's ``rebuild_sweeps``; 0 in a round that kept its
sketches)."""
from bench import jobspan


def read(r):
    return jobspan.counter(r, "rebuild_sweeps")
