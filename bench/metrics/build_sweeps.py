"""Fixpoint sweeps of the traced job's first build, as the program returns
them (``InfluenceResult.propagate_iters``)."""


def read(r):
    return float(r.answer["build_sweeps"])
