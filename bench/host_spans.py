"""The program's host spans in a profiler trace, summed by name.

The program's tracer (``repro.obs.trace.Tracer`` with
``jax_annotations=True``) puts each live span on the host plane of a
``jax.profiler`` trace, on the device's clock.  ``reduce_spans`` reduces
such a trace as ``bench.trace_reduce.reduce_trace`` does, with the same
device numbers, names idle gaps after the pipeline's spans (``prep.*``)
as well, and adds the summed duration of every host span by exact name,
clipped to the window (the span ``bench.window``; the whole trace where
that span is absent).  Spans of one name on several threads add up.

``bench/run.py --trace 1`` reduces with ``reduce_trace`` and leaves the
program's tracer off, so the readers built on ``window_share`` find no
span there and report nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from bench.trace_reduce import (HOST_SPAN_PREFIXES, WINDOW_SPAN,
                                TraceSummary, reduce_trace)

PROGRAM_SPAN_PREFIXES = HOST_SPAN_PREFIXES + ("prep.",)


@dataclasses.dataclass
class SpanSummary(TraceSummary):
    """A ``TraceSummary`` with the host spans' summed seconds by name."""

    host_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def span_s(self, name: str) -> Optional[float]:
        """Summed seconds of the host spans named ``name`` inside the
        window; None where no such span ran there."""
        return self.host_s.get(name)


def host_span_seconds(path: str, window_span: str = WINDOW_SPAN
                      ) -> Dict[str, float]:
    """Summed seconds of every host-plane event by exact name, clipped
    to the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events = [(e.start_ns, e.end_ns, e.name) for plane in pd.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    marks = [(s, e) for s, e, n in events if n == window_span]
    t0 = min((s for s, _ in marks), default=float("-inf"))
    t1 = max((e for _, e in marks), default=float("inf"))
    out: Dict[str, float] = {}
    for s, e, name in events:
        d = min(e, t1) - max(s, t0)
        if d > 0:
            out[name] = out.get(name, 0.0) + d * 1e-9
    return out


def reduce_spans(path: str, *, window_span: str = WINDOW_SPAN,
                 top_gaps: int = 10) -> SpanSummary:
    """``reduce_trace`` with the program's span prefixes, plus the host
    spans' summed seconds (see the module docstring)."""
    base = reduce_trace(path, window_span=window_span,
                        host_prefixes=PROGRAM_SPAN_PREFIXES,
                        top_gaps=top_gaps)
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(base)}
    return SpanSummary(**fields,
                       host_s=host_span_seconds(path, window_span))


def window_share(rec, name: str) -> Optional[float]:
    """The summed time of the host span ``name`` over the traced window,
    in %; None where the run's trace holds no such span or was reduced
    without host spans."""
    span_s = getattr(rec.trace, "span_s", None)
    t = span_s(name) if span_s is not None else None
    return 100.0 * t / rec.trace.window_s if t is not None else None
