"""Seconds per seed round: the traced job less its build, over K (host
clock)."""


def read(r):
    return (r.job_s - r.build_s) / int(r.config["k"])
