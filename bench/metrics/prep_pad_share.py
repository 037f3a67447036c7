"""Share of the traced window in which the loader thread built the
padded index and mask arrays of a chunk (the program span
``prep.pad``, summed), in %."""

from bench.host_spans import window_share


def read(rec):
    return window_share(rec, "prep.pad")
