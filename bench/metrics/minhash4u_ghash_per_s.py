"""4U hash evaluations (real nonzeros x k, counted by ``bench.work``)
per second of the 4U minhash kernel's device time in the trace
(``_minhash4u_run``), in 1e9/s.  A rate: v5e publishes no peak for the
integer work hashing runs on."""


def read(rec):
    evals = rec.stats.get("hash_evals")
    t = rec.trace.kernel_s("minhash4u") if rec.trace else None
    return evals / t / 1e9 if evals and t else None
