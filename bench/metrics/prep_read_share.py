"""Share of the traced window in which the loader thread read raw
shards: the ``.npz`` read and the split into row views (the program
span ``prep.read``, summed), in %."""

from bench.host_spans import window_share


def read(rec):
    return window_share(rec, "prep.read")
