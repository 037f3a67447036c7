#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for the trace reducer's test.

    python3 bench/record_trace.py OUT_DIR

Runs a few kernels of the main path at small shapes (a packed 2U minhash
call, a packed-Hamming match, a jitted elementwise op) inside host
``TraceAnnotation`` spans, with one deliberate idle gap (a host sleep
inside the span ``record.sleep``), and writes the profiler's
``.xplane.pb`` under OUT_DIR.  It then prints what the trace holds:
planes, lines, event counts and the first event names of each line.
The committed fixture under ``tests/bench/data/`` came from this script.
"""

from __future__ import annotations

import glob
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(out_dir: str) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())}")
    if dev.platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from repro.data.sparse import SparseBatch
    from repro.kernels import PackedSignatures, SignatureEngine, packed_match
    from repro.kernels.pack import PackSpec
    from repro.core.hashing import Hash2U

    rng = np.random.default_rng(0)
    idx = rng.integers(0, 1 << 24, size=(256, 256), dtype=np.int32)
    batch = SparseBatch(jnp.asarray(idx), jnp.ones((256, 256), bool), None)
    fam = Hash2U.create(jax.random.PRNGKey(0), 512, 24)
    eng = SignatureEngine(fam, b=8, packed=True)
    corpus = jnp.asarray(rng.integers(0, 2**32, size=(1024, 128),
                                      dtype=np.uint32))
    spec = PackSpec(512, 8)
    add = jax.jit(lambda a: a * 3 + 1)

    def work():
        with jax.profiler.TraceAnnotation("record.minhash"):
            words = eng.packed_signatures(batch).data
            words.block_until_ready()
        with jax.profiler.TraceAnnotation("record.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("record.hamming"):
            m = packed_match(words[:8], corpus, spec)
            m.block_until_ready()
        with jax.profiler.TraceAnnotation("record.add"):
            add(corpus).block_until_ready()

    work()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    path = paths[-1]
    print(f"trace {path} bytes={os.path.getsize(path)}")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:6]:
                stats = {k: v for k, v in e.stats} if e.stats else {}
                print(f"    {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={str(stats)[:300]}")
    print("memory_stats", dev.memory_stats())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else ".bench_work/trace"))
