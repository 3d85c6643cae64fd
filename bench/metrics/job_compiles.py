"""Backend compiles during the traced job (the job span's ``compiles``,
from the program's ``jit.compiles`` counter): 0 once the warm-up has
compiled the job's program."""
from bench import jobspan


def read(r):
    return jobspan.counter(r, "compiles")
