"""95th percentile of the server's queue wait (admission to batch
pop, ``ServerStats.queue_wait_s``) over the window's flushes, in ms."""


def read(rec):
    return rec.stats.get("queue_wait_p95_ms")
