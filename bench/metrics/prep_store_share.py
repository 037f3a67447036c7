"""Share of the window the pipeline spent writing ``.sig`` shards
(``PreprocessStats.store_s`` summed over the passes), in %."""


def read(rec):
    s = rec.stats
    return 100.0 * s["store_s"] / s["window_s"] if "store_s" in s else None
