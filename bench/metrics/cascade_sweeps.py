"""Cascade sweeps of the traced job, summed over its seed rounds, as the
program counts them (the job span's ``cascade_sweeps``)."""
from bench import jobspan


def read(r):
    return jobspan.counter(r, "cascade_sweeps")
