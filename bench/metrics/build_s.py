"""Seconds of the traced ``InfluenceSession.build_sketch_matrix`` call (fill
and fixpoint), host clock, ended by ``block_until_ready``."""


def read(r):
    return r.build_s
