"""The harness is driven by data: a new cell, configuration or per-layer
metric is a new file, found by its name; and a host without a TPU gets
no result."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as harness

from perfbench_util import CELLS, REPO, run_cell


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_files(tiny_root):
    b = os.path.join(tiny_root, "bench")
    before = digest(tiny_root)
    shutil.copy(os.path.join(b, "configs", "webspam.json"),
                os.path.join(b, "configs", "webspam_b.json"))
    shutil.copy(os.path.join(b, "configs", "webspam_ref.py"),
                os.path.join(b, "configs", "webspam_b_ref.py"))
    traffic = json.load(open(os.path.join(b, "traffic", "prep.json")))
    traffic.update(shards=3, rows_per_shard=64, chunk_size=64)
    json.dump(traffic, open(os.path.join(b, "traffic", "prep-3.json"), "w"))
    with open(os.path.join(b, "metrics", "prep_passes.py"), "w") as f:
        f.write("def read(rec):\n    return float(rec.stats['passes'])\n")
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["configs"].append(dict(spec["configs"][0], name="webspam_b",
                                file="bench/configs/webspam_b.json"))
    spec["workloads"].append({"name": "webspam_b-prep3",
                              "config": "webspam_b", "traffic": "prep-3",
                              "chips": 1, "why": "a cell added as files"})
    for m in spec["end_to_end"]:
        if m["name"] == "prep_rows_per_s":
            m["workloads"].append("webspam_b-prep3")
    spec["per_layer"].append({"name": "prep_passes", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "data pipeline",
                              "moves": "prep_rows_per_s",
                              "workloads": ["webspam_b-prep3"]})
    json.dump(spec, open(spec_path, "w"))

    out = run_cell(tiny_root, "webspam_b-prep3", seed=2**33 + 1)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"prep_rows_per_s", "setup_s"}
    assert out["attempted"] % (3 * 64) == 0
    e2e, layer = harness.cell_metrics(spec, "webspam_b-prep3",
                                      {"prep_rows_per_s": 1.0})
    assert [m["name"] for m in layer] == ["prep_passes"]
    rec = types.SimpleNamespace(stats={"passes": 4})
    assert harness.read_layer(tiny_root, layer[0], rec) == 4.0
    after = digest(tiny_root)
    assert all(after[p] == h for p, h in before.items())


def test_metrics_of_each_cell():
    spec = json.load(open(CELLS))
    e2e, layer = harness.cell_metrics(spec, "rcv1x-lsh",
                                      {"search_p95_ms": 1.0})
    assert {m["name"] for m in e2e} == {"search_p95_ms", "setup_s"}
    assert {m["name"] for m in layer} == {
        "serve_queue_wait_p95_ms", "lsh_candidates_per_query",
        "device_idle_share.search"}


@pytest.mark.parametrize("path", [os.path.join(REPO, "BENCHMARK.json"),
                                  CELLS])
def test_every_named_file_exists(path):
    spec = json.load(open(path))
    bench = os.path.join(REPO, "bench")
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert os.path.exists(os.path.join(bench, "configs",
                                           c["name"] + "_ref.py"))
    for w in spec["workloads"]:
        traffic = json.load(open(os.path.join(bench, "traffic",
                                              w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(bench, "entries",
                                           traffic["entry"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(bench, "metrics",
                                           m["name"] + ".py"))


def bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "webspam-prep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(proc):
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return not any(ln.lstrip().startswith("{") for ln in lines)


def test_cpu_host_exits_nonzero_without_result(tmp_path):
    proc = bench_cmd(REPO, {"JAX_COMPILATION_CACHE_DIR":
                            str(tmp_path / "cache")})
    assert proc.returncode != 0
    assert no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = bench_cmd(str(tmp_path))
    assert proc.returncode != 0
    assert no_result(proc)
