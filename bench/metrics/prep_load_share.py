"""Share of the window the pipeline spent loading raw shards
(``PreprocessStats.load_s`` summed over the passes), in %."""


def read(rec):
    s = rec.stats
    return 100.0 * s["load_s"] / s["window_s"] if "load_s" in s else None
