"""Share of the traced window in which no operation ran on the device
(1 - busy / window, from the profiler trace), in %."""


def read(rec):
    return 100.0 * rec.trace.idle_share if rec.trace else None
