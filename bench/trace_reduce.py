"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

From the device planes (``/device:TPU:<i>``, line ``XLA Ops``) it takes
every operation's interval inside the measured window, which the
harness marks with the host span ``bench.window``:

  * busy time: the union of the operation intervals (per chip, then
    averaged over the chips);
  * time per operation, summed under a stable name: the HLO instruction
    name with its numeric suffix dropped (``%_minhash2u_run.1 = ...``
    becomes ``_minhash2u_run``), which names a Pallas kernel after the
    jitted function that calls it;
  * idle gaps: the stretches of the window in which no operation ran on
    chip 0, each named after the innermost host span open at its middle
    (a harness span ``bench.*``, a server flush ``flush:*`` or a jitted
    dispatch ``PjitFunction(...)``), or ``(no host span)``.

The device's clock runs one to two milliseconds ahead of the host's in
the traces seen on a v5e (an operation appears to start before the host
launched it), so a gap shorter than that may be named after a
neighbouring span; the window, seconds long, is not affected.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIXES = ("bench.", "flush:", "PjitFunction(")
PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")


def peaks_for(device_kind: str, path: str = PEAKS_PATH) -> dict:
    """The published peaks of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source")
    return table[device_kind]


def stable_name(op_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = op_text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """The complement of merged ``busy`` intervals inside [t0, t1]."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class TraceSummary:
    """What one traced window holds; times in seconds."""

    window_s: float
    busy_s: float                      # averaged over the chips
    chips: int
    op_s: Dict[str, float]             # stable op name -> device seconds
    idle_gaps: List[Tuple[str, float]]  # longest first, chip 0

    def kernel_s(self, pattern: str) -> Optional[float]:
        """Summed device time of the ops whose stable name contains
        ``pattern``; None where no such op ran."""
        hits = [s for name, s in self.op_s.items() if pattern in name]
        return sum(hits) if hits else None

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def _host_spans(pd, prefixes: Sequence[str]):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    spans.append((e.start_ns, e.end_ns, e.name))
    return spans


def _name_gap(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "(no host span)"


def reduce_trace(path: str, *, window_span: str = WINDOW_SPAN,
                 host_prefixes: Sequence[str] = HOST_SPAN_PREFIXES,
                 top_gaps: int = 10) -> TraceSummary:
    """Read one ``.xplane.pb`` and reduce it (see the module docstring).

    Raises ``ValueError`` when the trace has no device plane or no
    operation ran on the device inside the window.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = _host_spans(pd, tuple(host_prefixes) + (window_span,))
    marks = [(s, e) for s, e, n in spans if n == window_span]
    planes = sorted((p for p in pd.planes if _DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    per_chip = []
    for plane in planes:
        ops = [(e.start_ns, e.end_ns, e.name) for line in plane.lines
               if line.name == "XLA Ops" for e in line.events]
        per_chip.append(ops)
    if not per_chip or not any(per_chip):
        raise ValueError(f"{path}: no operation on any device plane")
    if marks:
        t0, t1 = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        t0 = min(s for ops in per_chip for s, _, _ in ops)
        t1 = max(e for ops in per_chip for _, e, _ in ops)
    busy_total, op_s, idle = 0.0, {}, []
    for chip, ops in enumerate(per_chip):
        clipped = [(max(s, t0), min(e, t1), n) for s, e, n in ops
                   if e > t0 and s < t1]
        busy = merge([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in busy)
        if chip == 0:
            for s, e, n in clipped:
                key = stable_name(n)
                op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
            idle = sorted(gaps(busy, t0, t1), key=lambda g: g[0] - g[1])
    if busy_total <= 0:
        raise ValueError(f"{path}: no device operation inside the window")
    named = [(_name_gap(spans, (s + e) / 2), (e - s) * 1e-9)
             for s, e in idle[:top_gaps]]
    return TraceSummary(window_s=(t1 - t0) * 1e-9,
                        busy_s=busy_total / len(per_chip) * 1e-9,
                        chips=len(per_chip), op_s=op_s,
                        idle_gaps=named)


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = []
    for root, _, files in os.walk(log_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)
