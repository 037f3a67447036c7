"""The benchmark's seeded generators: deterministic per seed, the same
sizes for every seed, and the statistics the configurations state."""

import json
import os

import numpy as np
import pytest

from bench import gen

from perfbench_util import REPO

WEBSPAM = json.load(open(os.path.join(REPO, "bench/configs/webspam.json")))
RCV1X = json.load(open(os.path.join(REPO, "bench/configs/rcv1x.json")))
BIG_SEED = 2**33 + 7          # more than 32 bits


def test_row_lengths_mean_is_webspams():
    a = WEBSPAM["assumed"]
    lens = gen.row_lengths(a["nnz_min"], a["nnz_max"], 4096)
    assert lens.min() == a["nnz_min"] and lens.max() == a["nnz_max"]
    assert abs(lens.mean() - WEBSPAM["nnz_mean"]) < 1.0


def test_seeds_beyond_32_bits_stay_distinct():
    assert not np.array_equal(gen.seed_state(BIG_SEED, 1),
                              gen.seed_state(7, 1))
    assert not np.array_equal(gen.seed_state(2**31 + 5, 1),
                              gen.seed_state(5, 1))
    with pytest.raises(ValueError):
        gen.seed_state(-1, 1)


def test_hash_coefficients():
    a1, a2 = gen.hash_coefficients(BIG_SEED, 512)
    b1, b2 = gen.hash_coefficients(BIG_SEED, 512)
    assert np.array_equal(a1, b1) and np.array_equal(a2, b2)
    assert a1.dtype == np.uint32 and np.all(a2 & 1)
    assert not np.array_equal(a1, gen.hash_coefficients(3, 512)[0])


def small_webspam():
    cfg = json.loads(json.dumps(WEBSPAM))
    cfg["assumed"].update(nnz_min=300, nnz_max=340)
    return cfg


def read_all(paths):
    out = []
    for p in paths:
        with np.load(p) as z:
            out.append((z["indices"].copy(), z["offsets"].copy(),
                        z["labels"].copy()))
    return out


def test_webspam_shards_deterministic(tmp_path):
    cfg = small_webspam()
    p1, n1 = gen.webspam_shards(cfg, BIG_SEED, 2, 64, str(tmp_path / "a"))
    p2, n2 = gen.webspam_shards(cfg, BIG_SEED, 2, 64, str(tmp_path / "b"))
    p3, n3 = gen.webspam_shards(cfg, 5, 2, 64, str(tmp_path / "c"))
    a, b, c = read_all(p1), read_all(p2), read_all(p3)
    for x, y in zip(a, b):
        assert all(np.array_equal(u, v) for u, v in zip(x, y))
    assert not np.array_equal(a[0][0], c[0][0])
    # every seed gets the same sizes, in another order
    assert n1 == n2 == n3
    lens = [np.sort(np.diff(s[1])) for s in a + c]
    assert all(np.array_equal(lens[0], x) for x in lens)
    idx, off, labels = a[0]
    assert idx.dtype == np.int32 and idx.min() >= 0
    assert idx.max() < cfg["D"]
    assert set(np.unique(labels)) <= {-1.0, 1.0}
    rows, lab = gen.read_rows(p1[0], np.array([0, 5]))
    assert np.array_equal(rows[1], idx[off[5]:off[6]]) and lab[1] == labels[5]


def test_webspam_rows_share_their_prototype(tmp_path):
    cfg = small_webspam()
    paths, _ = gen.webspam_shards(cfg, 9, 1, 256, str(tmp_path))
    idx, off, labels = read_all(paths)[0]
    rows = [set(idx[off[i]:off[i + 1]]) for i in range(256)]
    # same-class pairs that share a prototype overlap by about 0.7^2 of a
    # row; most cross-class pairs share nothing
    same = max(len(rows[0] & r) for r in rows[1:])
    assert same > 0.3 * len(rows[0])


def small_rcv1x(n=4096):
    cfg = json.loads(json.dumps(RCV1X))
    cfg["n"] = n
    return cfg


def codes(words, b=8):
    per = 32 // b
    sh = np.arange(per, dtype=np.uint32) * b
    return ((words[:, :, None] >> sh) & ((1 << b) - 1)).reshape(
        words.shape[0], -1)


def test_corpus_deterministic_and_clustered():
    cfg = small_rcv1x()
    w1 = gen.rcv1x_corpus(cfg, BIG_SEED, block=1024)
    w2 = gen.rcv1x_corpus(cfg, BIG_SEED, block=1024)
    assert w1.shape == (4096, 128) and w1.dtype == np.uint32
    assert np.array_equal(w1, w2)
    assert not np.array_equal(w1, gen.rcv1x_corpus(cfg, 3, block=1024))
    c = codes(w1)
    # members of one cluster agree on about R1 * R2 of their codes,
    # members of different clusters on about 2^-b
    within = np.mean(c[0] == c[1])
    across = np.mean(c[0] == c[16])
    assert within > 0.2 and across < 0.05


@pytest.mark.parametrize("r", [0.5, 0.8, 0.95])
def test_near_duplicate_agreement_matches_r(r):
    cfg = small_rcv1x(1024)
    words = gen.rcv1x_corpus(cfg, 4, block=1024)
    src = np.arange(200)
    q = gen.rcv1x_queries(cfg, words, src, np.full(200, r, np.float32), 4)
    agree = np.mean(codes(q) == codes(words[src]))
    # Theorem 1, sparse limit: P[equal] = R + (1 - R) 2^-b; 102,400
    # codes put the standard error near 0.0015
    assert abs(agree - (r + (1 - r) / 256)) < 0.01


def test_query_schedule_same_sizes_every_seed():
    tr = json.load(open(os.path.join(REPO, "bench/traffic/dedup-exact.json")))
    cfg = small_rcv1x()
    d1, s1, r1 = gen.query_schedule(cfg, tr, BIG_SEED, 10.0)
    d2, s2, r2 = gen.query_schedule(cfg, tr, BIG_SEED, 10.0)
    d3, _, r3 = gen.query_schedule(cfg, tr, 17, 10.0)
    assert np.array_equal(d1, d2) and np.array_equal(s1, s2)
    m = round(tr["rate_qps"] * 10.0)
    assert len(d1) == len(d3) == m
    assert d1[0] == 0 and np.all(np.diff(d1) >= 0)
    assert 9.5 < d1[-1] < 10.0 and 9.5 < d3[-1] < 10.0
    assert np.array_equal(np.sort(r1), np.sort(r3))
    assert np.sum(r1 > 0) == round(tr["dup_share"] * m)
    assert r1[r1 > 0].min() >= tr["dup_r_min"]
    assert r1.max() <= tr["dup_r_max"]
