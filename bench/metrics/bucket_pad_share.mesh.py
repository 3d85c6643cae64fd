"""Share of the mesh partition's bucket slots that hold no edge, over the
propagate and cascade buckets of every ring step and shard: the job span's
``bucket_pad_share``. None where the program records no such attribute."""
from bench import jobspan


def read(r):
    share = jobspan.counter(r, "bucket_pad_share")
    return None if share is None else 100.0 * share
