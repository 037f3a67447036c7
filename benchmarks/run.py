"""Benchmark harness: one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME...]]
            [--repeat N] [--json PATH]
Prints ``name,us_per_call,derived`` CSV (one line per measurement).

``--only`` with a single token is a substring filter (legacy behaviour);
a comma-separated list selects exact module names and errors on unknown
ones (no more silently matching nothing on a typo).  ``--repeat N`` runs
each selected module N times and reports the per-row MEDIAN wall-clock
(plus min/max spread), so scaling numbers stop being single-sample
noise; ``--json PATH`` writes a ``{"rows": [...], "metrics": {...}}``
artifact -- ``rows`` is the measurement list, ``metrics`` maps each
module to the ``repro.obs`` registry snapshot taken right after it ran
(the registry is reset before each module, so snapshots don't bleed
across modules).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks.common import fmt_rows

MODULES = [
    ("preprocessing_cpu", "Table 2"),
    ("preprocessing_kernel", "Table 3 / Figs 1-3"),
    ("preprocessing_oph", "OPH vs §3 k-pass cost"),
    ("learning_hashfuncs", "Fig 4"),
    ("learning_oph_parity", "Fig 4-style OPH vs minhash parity"),
    ("vw_hashfuncs", "Fig 5"),
    ("learning_scaling", "Figs 6-9"),
    ("bbit_vs_vw", "Figs 10-12"),
    ("online_learning", "Figs 13-15, 19"),
    ("loading_time", "Figs 16, 18 / Table 4"),
    ("resemblance_mse", "Figs 20-22 / App. A"),
    ("signature_engine", "§6 / Table 2 wire format"),
    ("search_index", "§1 search workload (repro.index)"),
    ("search_scaling", "serving scale-out (fused scan, shards, "
                       "out-of-core)"),
    ("search_serving", "continuous-batching server (latency vs load, "
                       "live appends)"),
]


def _selector(only):
    """--only matcher: single token = substring, comma list = exact names."""
    if not only:
        return lambda name: True
    tokens = [t.strip() for t in only.split(",") if t.strip()]
    if len(tokens) > 1:
        known = {name for name, _ in MODULES}
        unknown = [t for t in tokens if t not in known]
        if unknown:
            raise SystemExit(f"--only: unknown module(s) {unknown}; "
                             f"available: {sorted(known)}")
        return lambda name: name in tokens
    return lambda name: tokens[0] in name


def _median_merge(runs):
    """Per-row median wall-clock over aligned repeat runs.

    Rows align by position and name (every module emits a deterministic
    row list); the derived dict comes from the median run, annotated
    with the repeat count and the min/max spread.
    """
    if len(runs) == 1:
        return runs[0]
    if any(len(r) != len(runs[0]) or
           [name for name, _, _ in r] != [name for name, _, _ in runs[0]]
           for r in runs[1:]):
        # misaligned rows (a module emitted differently across repeats):
        # fall back to the last run rather than mismatching medians
        return runs[-1]
    merged = []
    for j, (name, _, _) in enumerate(runs[0]):
        order = sorted(range(len(runs)), key=lambda i: runs[i][j][1])
        mid = order[len(order) // 2]
        us = runs[mid][j][1]
        derived = dict(runs[mid][j][2])
        derived.update(repeat=len(runs),
                       us_min=round(runs[order[0]][j][1], 3),
                       us_max=round(runs[order[-1]][j][1], 3))
        merged.append((name, us, derived))
    return merged


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter, or comma-separated exact "
                         "module names")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="run each selected module N times; report the "
                         "per-row median wall-clock")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the final rows as a JSON artifact")
    args = ap.parse_args()
    if args.repeat < 1:
        ap.error("--repeat must be >= 1")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    selected = _selector(args.only)

    from repro.obs.metrics import get_registry

    all_rows = []
    metrics = {}
    failures = []
    ran = 0
    for mod_name, paper_ref in MODULES:
        if not selected(mod_name):
            continue
        ran += 1
        t0 = time.perf_counter()
        get_registry().reset()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = _median_merge([mod.run() for _ in range(args.repeat)])
            all_rows.extend(rows)
            snap = get_registry().snapshot()
            if snap:
                metrics[mod_name] = snap
            dt = time.perf_counter() - t0
            print(f"# {mod_name} ({paper_ref}): {len(rows)} rows "
                  f"in {dt:.1f}s"
                  + (f" ({args.repeat} repeats, median reported)"
                     if args.repeat > 1 else ""), file=sys.stderr)
        except Exception:
            failures.append(mod_name)
            print(f"# {mod_name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    print(fmt_rows(all_rows))
    if args.json and not failures:
        doc = {"rows": [{"name": name, "us_per_call": us, **derived}
                        for name, us, derived in all_rows],
               "metrics": metrics}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
    if not ran:
        # a substring --only matching nothing must not look like success
        print(f"# --only {args.only!r} selected no modules; available: "
              f"{sorted(name for name, _ in MODULES)}", file=sys.stderr)
        sys.exit(2)
    if failures:
        # a raising module is a harness failure, not a summary footnote:
        # CI must go red
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
