"""Host spans summed by name from the committed v5e trace, and the
readers of the pipeline's span shares.

``data/v5e_small.xplane.pb`` holds four host spans ``record.*``, one of
them a 20 ms sleep (``bench/record_trace.py``)."""

import dataclasses
import os
import types

import pytest

from bench.host_spans import SpanSummary, host_span_seconds, reduce_spans
from bench.run import load_module
from bench.trace_reduce import reduce_trace

from perfbench_util import REPO

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_small.xplane.pb")
SLEEP_S = (72955889 - 51858410) * 1e-9
READERS = {"prep_wait_share": "prep.wait", "prep_read_share": "prep.read",
           "prep_pad_share": "prep.pad", "prep_upload_share": "prep.upload"}


def test_span_seconds_clipped_to_the_window():
    s = reduce_spans(FIXTURE, window_span="record.sleep")
    assert s.span_s("record.sleep") == pytest.approx(SLEEP_S)
    assert s.span_s("prep.read") is None
    # the minhash call ended before the window opened
    assert s.span_s("record.minhash") is None
    whole = host_span_seconds(FIXTURE, window_span="absent")
    assert whole["record.sleep"] == pytest.approx(SLEEP_S)
    assert whole["record.minhash"] == pytest.approx(1344931e-9)
    assert whole["record.add"] == pytest.approx(547660e-9)


@pytest.mark.parametrize("window", ["record.sleep", "absent"])
def test_device_numbers_as_reduce_trace_gives_them(window):
    base = reduce_trace(FIXTURE, window_span=window,
                        host_prefixes=("record.",))
    s = reduce_spans(FIXTURE, window_span=window)
    for f in ("window_s", "busy_s", "chips", "op_s"):
        assert getattr(s, f) == getattr(base, f)
    assert [g for _, g in s.idle_gaps] == [g for _, g in base.idle_gaps]


def reader(name):
    return load_module(os.path.join(REPO, "bench", "metrics", name + ".py"),
                       "test_reader_" + name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_report_only_their_span(name):
    plain = reduce_trace(FIXTURE, window_span="absent",
                         host_prefixes=("record.",))
    fields = {f.name: getattr(plain, f.name)
              for f in dataclasses.fields(plain)}
    read = reader(name).read
    for trace in (None, plain, SpanSummary(**fields),
                  SpanSummary(**fields, host_s={"prep.other": 1.0})):
        assert read(types.SimpleNamespace(trace=trace)) is None
    spans = SpanSummary(**fields, host_s={READERS[name]: 0.25 *
                                          plain.window_s})
    assert read(types.SimpleNamespace(trace=spans)) == pytest.approx(25.0)
