#!/usr/bin/env python3
"""Sweep the offered rate of a search cell to find its knee.

    python3 bench/sweep.py --workload rcv1x-exact --seed N \\
        --rates 100,200,400 [--seconds 8]

One set-up, then one open-loop window per rate, lowest first, each with
the cell's own query mix at that rate.  Per rate it prints one JSON line:
the offered and the answered rate, latency percentiles from due time to
answer, the p95 of the first and of the last quarter of the requests
(a queue that grows shows as a last quarter far above the first), and
the client's lateness.  The knee is the highest rate whose answered rate
keeps up and whose last quarter does not grow; the cell runs at 0.8 of
it, written into its traffic file as ``rate_qps``.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import gen                        # noqa: E402
from bench import run as harness             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    harness.prepare()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell, _, cfg, traffic = harness.find_cell(harness.ROOT, args.workload)
    harness.device_info(int(cell["chips"]), True)
    entry = harness.load_module(
        os.path.join(harness.ROOT, "bench", "entries", "search.py"),
        "bench_entry_search")
    ctx = harness.Context(os.path.join(harness.ROOT, ".bench_work",
                                       "sweep"), False, True)
    st = entry.setup(cfg, traffic, args.seed, args.seconds, ctx)
    harness.settle()
    for rate in sorted(float(r) for r in args.rates.split(",")):
        tr = dict(traffic, rate_qps=rate)
        due, src, r = gen.query_schedule(cfg, tr, args.seed, args.seconds)
        st.update(due=due, dup=r > 0,
                  queries=gen.rcv1x_queries(cfg, st["words"], src, r,
                                            args.seed))
        entry.window(st, args.seconds)
        lat = st["latency"] * 1e3
        q = len(lat) // 4
        served = np.isfinite(lat)
        res = entry.results(st)
        print(json.dumps({
            "rate_qps": rate, "requests": len(lat),
            "answered_qps": float(served.sum() / st["elapsed"]),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": res["end_to_end"]["search_p95_ms"],
            "p99_ms": float(np.percentile(lat, 99)),
            "first_quarter_p95_ms": float(np.percentile(lat[:q], 95)),
            "last_quarter_p95_ms": float(np.percentile(lat[-q:], 95)),
            "late_p99_ms": float(np.percentile(st["late"], 99) * 1e3),
            "mean_batch": (float(np.mean(res["stats"]["batch_sizes"]))
                           if res["stats"]["batch_sizes"] else 0.0),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
