"""Share of the HBM roofline the Hamming kernel reaches in the exact
scan, in %: the least bytes the window's flushes must move
(``bench.work.exact_window_bytes``) at the peak HBM bandwidth, over the
summed device time of the kernel.  HBM is the bound: v5e publishes no
peak for the integer field compares the kernel runs on."""

from bench import work


def read(rec):
    s = rec.stats
    t = rec.trace.kernel_s("packed_match") if rec.trace else None
    if (rec.traffic.get("mode") != "exact" or not t
            or not s.get("batch_sizes") or rec.peaks is None):
        return None
    least = work.exact_window_bytes(s["n_docs"], s["words"],
                                    s["batch_sizes"], s["topk"])
    return 100.0 * least / rec.peaks["hbm_bytes_per_s"] / t
