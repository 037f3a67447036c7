"""The trace reduction, the peaks table and the work counts.

``data/v5e_small.xplane.pb`` was recorded on one TPU v5e by
``bench/record_trace.py``: a packed 2U minhash call, a 20 ms host sleep,
a packed-Hamming match and a jitted elementwise op, each inside a host
span ``record.*``.
"""

import os

import pytest

from bench import work
from bench.trace_reduce import (gaps, merge, peaks_for, reduce_trace,
                                stable_name)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return reduce_trace(FIXTURE, window_span="record.sleep",
                        host_prefixes=("record.",))


def test_merge_and_gaps():
    busy = merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert gaps([], 0, 4) == [(0, 4)]


@pytest.mark.parametrize("text,name", [
    ("%_minhash2u_run.1 = u32[128,256]{1,0} custom-call(...)",
     "_minhash2u_run"),
    ("%copy-done = s32[256] copy-done(...)", "copy-done"),
    ("%constant_dynamic-slice_fusion = u32[8,128] fusion(...)",
     "constant_dynamic-slice_fusion"),
])
def test_stable_name(text, name):
    assert stable_name(text) == name


def test_whole_trace_when_no_window_span():
    s = reduce_trace(FIXTURE, window_span="absent",
                     host_prefixes=("record.",))
    assert s.chips == 1
    # the four calls' ops, hand-summed from the fixture
    assert s.kernel_s("minhash") == pytest.approx(86157e-9)
    assert s.kernel_s("packed_match") == pytest.approx(11023e-9)
    assert s.kernel_s("no_such_kernel") is None
    assert 0 < s.busy_s < s.window_s
    # the longest idle gap is the 20 ms host sleep
    name, secs = s.idle_gaps[0]
    assert name == "record.sleep"
    assert 0.019 < secs < 0.025
    assert s.idle_share > 0.9


def test_window_span_clips(summary):
    # the span record.sleep (host clock) ends just after the device clock
    # shows the Hamming match: the minhash call lies outside the window
    assert summary.window_s == pytest.approx((72955889 - 51858410) * 1e-9)
    assert summary.kernel_s("minhash") is None
    assert summary.kernel_s("packed_match") == pytest.approx(11023e-9)
    bd = summary.breakdown(top=3)
    assert bd["device_ops"][0][0] == "_packed_match_run"
    assert len(bd["device_ops"]) <= 3 and len(bd["idle_gaps"]) <= 3
    assert bd["idle_gaps"][0][0] == "record.sleep"


def test_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_hash_evaluations_by_hand():
    # 2 rows of 3 and 5 real nonzeros, k = 4: 8 * 4 hash evaluations
    assert work.hash_evaluations(3 + 5, 4) == 32
    assert work.hash_evaluations(0, 512) == 0
    with pytest.raises(ValueError):
        work.hash_evaluations(10, 0)


def test_exact_flush_bytes_by_hand():
    # 1000 rows of 128 words, 32 queries, top-10: corpus 512,000 B,
    # queries 16,384 B, ids and scores 32 * 10 * 8 = 2,560 B
    assert work.exact_flush_bytes(1000, 128, 32, 10) == 530944
    assert work.exact_window_bytes(1000, 128, [32, 1], 10) == (
        530944 + 4 * 128 * 1001 + 80)
    with pytest.raises(ValueError):
        work.exact_flush_bytes(0, 128, 1, 10)
