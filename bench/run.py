#!/usr/bin/env python3
"""The chip benchmark's single command.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Reads ``BENCHMARK.json`` at the root of the checkout and finds, by the
names there, every file of the cell: the configuration
(``bench/configs/<config>.json`` and its plain reference
``bench/configs/<config>_ref.py``), the traffic mix
(``bench/traffic/<traffic>.json``, whose ``entry`` names its module in
``bench/entries/``) and, for ``--trace 1``, one reader per per-layer
metric (``bench/metrics/<metric>.py``).  A new cell is new files and a
new entry in ``BENCHMARK.json``; nothing here changes.

A run: set-up (inputs from the seed, the program built, every shape the
window uses compiled; ``setup_s`` runs from the start of this process to
the start of the window), the window (``--seconds`` of timed work, with
the profiler on for ``--trace 1``), the device's peak memory, then the
check of what the window produced against the plain reference.  The
last line of standard output is one JSON object; the last lines of
standard error give each compared number beside its limit.  A host where
JAX finds no TPU, or fewer chips than the cell asks for, exits with 3
and prints no result.
"""

import time

T_START = time.monotonic()

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Failure(RuntimeError):
    """A run that must print no result (exit 3)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: str, name: str):
    """(cell entry, the whole spec, config dict, traffic dict) by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json "
                      f"({', '.join(sorted(cells))})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, spec, cfg, traffic


def cell_metrics(spec: dict, cell: str, entry_e2e):
    """(end-to-end metrics, per-layer metrics) that this cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])
           and (m["name"] == "setup_s" or m["name"] in entry_e2e)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def read_layer(root: str, metric: dict, rec) -> float:
    mod = load_module(os.path.join(root, "bench", "metrics",
                                   metric["name"] + ".py"),
                      "bench_metric_" + metric["name"].replace(".", "_"))
    return mod.read(rec)


class Context:
    """What an entry may ask of the harness."""

    def __init__(self, work: str, trace: bool, require_tpu: bool):
        self.work = work
        self.trace = trace
        self.require_tpu = require_tpu

    def span(self, name: str):
        """A host span on the profiler's clock (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def expect_backend(self, backend: str) -> None:
        if self.require_tpu and backend != "tpu":
            raise Failure(f"kernels resolve to backend {backend!r}, not tpu")


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) while
    ``active``: any program that the window compiles or loads."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise Failure(f"no TPU: JAX runs on {info}")
    if len(devs) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return info


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def settle() -> None:
    """End of set-up: collect its garbage and freeze what survives, so
    that no collection in the window walks the objects set-up left
    behind (compiled programs, index tables), as a long-running server
    would arrange."""
    gc.collect()
    gc.freeze()


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def run(args, *, root: str = ROOT, require_tpu: bool = True,
        readings=None) -> dict:
    """One run of one cell; returns the result object (raises Failure).

    ``readings(entry, st, ref, traffic)``, when given, is called after
    the check, while the run's files still exist; what it returns goes
    into the result under ``readings`` (the control runs use it)."""
    cell, spec, cfg, traffic = find_cell(root, args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    info = device_info(int(cell["chips"]), require_tpu)
    work = os.path.join(root, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    entry = load_module(os.path.join(root, "bench", "entries",
                                     traffic["entry"] + ".py"),
                        "bench_entry_" + traffic["entry"])
    ref = load_module(os.path.join(root, "bench", "configs",
                                   cell["config"] + "_ref.py"),
                      "bench_ref_" + cell["config"])
    ctx = Context(work, bool(args.trace), require_tpu)
    counter = CompileCounter()
    summary = None
    try:
        st = entry.setup(cfg, traffic, args.seed, args.seconds, ctx)
        settle()
        trace_dir = os.path.join(work, "trace")
        if ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.monotonic() - T_START
        counter.active = True
        try:
            with ctx.span("bench.window"):
                entry.window(st, args.seconds)
        finally:
            counter.active = False
            if ctx.trace:
                jax.profiler.stop_trace()
        res = entry.results(st)
        peak = memory_peak(int(cell["chips"]))
        entry.release(st)
        if ctx.trace:
            from bench.trace_reduce import find_trace, reduce_trace
            summary = reduce_trace(find_trace(trace_dir))
            shutil.rmtree(trace_dir)
        nums, notes = entry.check(st, ref, int(traffic["check_sample"]))
        extra = readings(entry, st, ref, traffic) if readings else None
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(f"[bench] compilations in window: {counter.count}")
    for key, val in res.get("notes", {}).items():
        say(f"[bench] {key}: {val}")
    e2e, layer = cell_metrics(spec, cell["name"], res["end_to_end"])
    device = dict(info, count=int(cell["chips"]), memory_peak_bytes=peak)
    out = {"correct": None, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if summary is not None:
        from bench.trace_reduce import peaks_for
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        rec = types.SimpleNamespace(cell=cell, cfg=cfg, traffic=traffic,
                                    stats=res["stats"], trace=summary,
                                    peaks=peaks_for(info["kind"])
                                    if require_tpu else None)
        for m in layer:
            val = read_layer(root, m, rec)
            if val is not None:
                out["metrics"][m["name"]] = {"value": val, "unit": m["unit"]}
        out["breakdown"] = summary.breakdown()
    else:
        vals = dict(res["end_to_end"], setup_s=setup_s)
        for m in e2e:
            out["metrics"][m["name"]] = {"value": vals[m["name"]],
                                         "unit": m["unit"]}
    limits = traffic["limits"]
    if set(limits) != set(nums):
        raise Failure(f"compared {sorted(nums)}, limits for {sorted(limits)}")
    out["correct"] = bool(res.get("complete", True)) and all(
        math.isfinite(nums[n]) and nums[n] <= limits[n] for n in nums)
    for key, val in notes.items():
        say(f"[bench] {key}: {val}")
    if extra is not None:
        out["readings"] = extra
    out["checks"] = {n: {"value": nums[n], "limit": limits[n]}
                     for n in sorted(nums)}
    for n in sorted(nums):
        say(f"check {n} = {nums[n]!r} (limit {limits[n]!r})")
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare() -> None:
    """Before JAX is imported: the program on the path, and JAX's
    persistent compilation cache at a fixed directory of the checkout
    (the path is part of each entry's key), whatever the environment
    says; and no TPU runtime logs, which would go to a fixed path under
    /tmp."""
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0 or args.seconds <= 0:
        say("bench: need --seed >= 0 and --seconds > 0")
        return 2
    prepare()
    try:
        out = run(args)
    except Failure as e:
        say(f"bench: {e}")
        return 3
    except ImportError as e:
        say(f"bench: run from a checkout of the repository ({e})")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
