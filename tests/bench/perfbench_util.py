"""Helpers of the chip benchmark's tests: a tiny copy of the benchmark
tree, so that whole runs of every cell fit on the CPU."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {
    "configs/webspam.json": {"n": 256, "k": 128,
                             "assumed": {"nnz_min": 150, "nnz_max": 250}},
    "configs/rcv1x.json": {"n": 2048, "k": 128, "n_bands": 42,
                           "rows_per_band": 3},
    "traffic/prep.json": {"shards": 2, "rows_per_shard": 128,
                          "chunk_size": 128, "check_sample": 16},
    "traffic/replay.json": {"shards": 2, "rows_per_shard": 128,
                            "chunk_size": 128, "batch_size": 32},
    "traffic/dedup-exact.json": {"rate_qps": 20, "max_batch": 4,
                                 "check_sample": 16},
    "traffic/dedup-lsh.json": {"rate_qps": 20, "max_batch": 4,
                               "check_sample": 16},
}


def edit_json(path, changes):
    with open(path) as f:
        data = json.load(f)
    for key, val in changes.items():
        if isinstance(val, dict) and isinstance(data.get(key), dict):
            data[key].update(val)
        else:
            data[key] = val
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


# every cell the entries can drive, whether or not BENCHMARK.json holds
# it yet: the tests cover each entry on its own
CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "benchmark_cells.json")


def make_tiny_root(dst):
    """The benchmark tree with every cell shrunk to CPU size."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(CELLS, os.path.join(dst, "BENCHMARK.json"))
    for rel, changes in TINY.items():
        edit_json(os.path.join(dst, "bench", rel), changes)
    return str(dst)


def run_cell(root, workload, seed=11, seconds=1.0, readings=None):
    """One whole run of a cell on the CPU, skipping the look for a chip."""
    from bench import run as harness
    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds)])
    return harness.run(args, root=root, require_tpu=False,
                       readings=readings)
