"""Share of the window the trainer spent in SGD steps
(``EpochStats.train_s`` summed over the epochs), in %."""


def read(rec):
    s = rec.stats
    return 100.0 * s["train_s"] / s["window_s"] if "epochs" in s else None
