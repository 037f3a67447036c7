"""Share of the traced window in which the loader thread handed a chunk
to the device: ``jnp.asarray`` of indices, mask and labels (the program
span ``prep.upload``, summed), in %."""

from bench.host_spans import window_share


def read(rec):
    return window_share(rec, "prep.upload")
