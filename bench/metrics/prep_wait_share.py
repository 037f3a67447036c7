"""Share of the traced window in which the caller of
``preprocess_shards`` was blocked on the loader's prefetch queue (the
program span ``prep.wait``, summed), in %: what ``prep_load_share``
reads from ``PreprocessStats.load_s``, on the profiler's clock."""

from bench.host_spans import window_share


def read(rec):
    return window_share(rec, "prep.wait")
