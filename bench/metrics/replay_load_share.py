"""Share of the window the trainer waited on the replayed cache
(``EpochStats.load_s`` summed over the epochs), in %."""


def read(rec):
    s = rec.stats
    return 100.0 * s["load_s"] / s["window_s"] if "epochs" in s else None
