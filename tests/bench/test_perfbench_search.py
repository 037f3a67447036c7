"""rcv1x-exact and rcv1x-lsh at CPU size: sound runs are correct; the
control (estimates in bfloat16 in the program's place) and faults
planted where answers are produced come out not correct."""

import numpy as np
import pytest

from repro.index.query import IndexSearcher, SearchResult

from perfbench_util import run_cell


def control_readings(entry, st, ref, traffic):
    return entry.control(st, ref, int(traffic["check_sample"]))


@pytest.mark.parametrize("cell", ["rcv1x-exact", "rcv1x-lsh"])
def test_sound_run_correct_and_control_not(tiny_root, cell):
    out = run_cell(tiny_root, cell, seed=5, seconds=1.5,
                   readings=control_readings)
    assert out["correct"] is True
    assert out["attempted"] == 30 and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    limits = {n: c["limit"] for n, c in out["checks"].items()}
    nums = out["readings"]
    assert any(nums[n] > limits[n] for n in nums), nums


def altered_answer(res):
    ids = res.indices.copy()
    ids[0, 0] = ids[0, 1]
    return SearchResult(ids, res.scores, res.n_candidates)


def half_batch(res):
    q = res.indices.shape[0]
    keep = np.arange(q) % max(1, (q + 1) // 2)
    return SearchResult(res.indices[keep], res.scores[keep],
                        None if res.n_candidates is None
                        else res.n_candidates[keep])


@pytest.mark.parametrize("cell,fault", [
    ("rcv1x-exact", altered_answer), ("rcv1x-exact", half_batch),
    ("rcv1x-lsh", altered_answer), ("rcv1x-lsh", half_batch)])
def test_fault_where_answers_are_produced_is_caught(tiny_root, monkeypatch,
                                                    cell, fault):
    orig = IndexSearcher.search

    def broken(self, queries, topk=10, **kw):
        return fault(orig(self, queries, topk, **kw))

    monkeypatch.setattr(IndexSearcher, "search", broken)
    out = run_cell(tiny_root, cell, seed=5, seconds=1.5)
    assert out["correct"] is False
