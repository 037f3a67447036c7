#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program's compared
numbers over many seeds, and the control's (and, for training, a planted
fault's) in the program's place, all in one process.

    python3 bench/control.py --workload NAME --seeds 11,12,13 \\
        [--control-seeds 3] [--seconds S]

Each seed is a whole run of the cell (set-up, a window of ``--seconds``,
the check); the first ``--control-seeds`` of them also read the
control: the plain reference in the next narrower precision put in the
program's place (``control`` of the cell's entry).  One JSON line per
seed; the control must come out above the limit on every seed, the
program below it.
"""

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as harness            # noqa: E402

FAULTS = {"online": ("bfloat16", "half")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    harness.prepare()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        def readings(entry, st, ref, traffic, control=i < args.control_seeds):
            if not control:
                return None
            kinds = FAULTS.get(traffic["entry"], ("control",))
            n = int(traffic["check_sample"])
            return {kind: (entry.control(st, ref, n, kind)
                           if kind != "control" else entry.control(st, ref, n))
                    for kind in kinds}
        run_args = harness.parse(["--workload", args.workload, "--seed",
                                  str(seed), "--seconds", str(args.seconds)])
        out = harness.run(run_args, readings=readings)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": {n: c["value"]
                                      for n, c in out["checks"].items()},
                          "limits": {n: c["limit"]
                                     for n, c in out["checks"].items()},
                          "readings": out.get("readings"),
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
