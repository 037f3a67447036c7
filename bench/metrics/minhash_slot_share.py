"""Share of the index slots the signature kernels hashed that held real
ids: the benchmark's own count of real nonzeros over the slots hashed
that the program reports (``PreprocessStats.slots_hashed``, segments
times segment width, padding included), summed over the window's
passes, in %.  None where the program reports no slots."""


def read(rec):
    s = rec.stats
    slots = s.get("slots_hashed")
    return 100.0 * s["nonzeros"] / slots if slots else None
