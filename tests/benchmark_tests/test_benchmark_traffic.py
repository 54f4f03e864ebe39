"""The traffic generator: total over seeds, the same for the same seed, the
same work in another order for another seed."""
import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark import traffic as T

TRAFFIC_DIR = os.path.join(ROOT, "benchmark", "traffic")
SERVING = [f[:-5] for f in sorted(os.listdir(TRAFFIC_DIR))
           if "prompt_tokens" in json.load(open(os.path.join(TRAFFIC_DIR, f)))]


def load(name):
    return json.load(open(os.path.join(TRAFFIC_DIR, name + ".json")))


@pytest.mark.parametrize("name", SERVING)
def test_generator_is_total_over_1000_seeds(name):
    t = dict(load(name), block=16)
    vocab, cache_len = 40478, 512
    seeds = list(range(990)) + [2**31 - 1, 2**31, 2**31 + 12345, 3000000019,
                                2**32 - 1, 2**32 + 7, 10**10, 10**12, 7**20,
                                2**63 + 1]
    assert len(seeds) == 1000
    for seed in seeds:
        src = T.RequestSource(t, seed, vocab, cache_len)
        for _ in range(16):
            r = src.next()
            plen = len(r["prompt"])
            assert 1 <= plen <= max(t["prompt_buckets"])
            assert r["prompt"].min() >= 0 and r["prompt"].max() < vocab
            assert r["max_new"] >= 1
            assert plen + r["max_new"] - 1 <= cache_len
            assert r["prompt"].dtype == np.int64


def test_lengths_are_clamped_where_a_file_asks_too_much():
    t = {"prompt_tokens": {"min": 1, "max": 900}, "prompt_buckets": [64, 128],
         "max_new_tokens": {"min": 1, "max": 900}, "block": 32}
    src = T.RequestSource(t, 3, 100, 160)
    for _ in range(64):
        r = src.next()
        assert len(r["prompt"]) <= 128
        assert len(r["prompt"]) + r["max_new"] - 1 <= 160


@pytest.mark.parametrize("name", SERVING)
def test_equal_seeds_give_equal_schedules(name):
    t = load(name)
    a, b, c = (T.RequestSource(t, s, 40478, 512) for s in (42, 42, 43))
    same = True
    for _ in range(300):
        ra, rb, rc = a.next(), b.next(), c.next()
        assert ra["max_new"] == rb["max_new"] and ra.get("due_s") == rb.get(
            "due_s") and np.array_equal(ra["prompt"], rb["prompt"])
        same = same and np.array_equal(ra["prompt"], rc["prompt"])
    assert not same


def test_every_seed_offers_the_same_work_in_another_order():
    t = load("doc_prefill_stratified")
    blocks = []
    for seed in (1, 2, 3000000019):
        src = T.RequestSource(t, seed, 40478, 512)
        reqs = [src.next() for _ in range(t["block"])]
        gaps = np.diff([0.0] + [r["due_s"] for r in reqs])
        blocks.append((sorted(len(r["prompt"]) for r in reqs),
                       sorted(r["max_new"] for r in reqs),
                       np.sort(np.round(gaps, 9)), reqs[-1]["due_s"]))
    for other in blocks[1:]:
        assert other[0] == blocks[0][0] and other[1] == blocks[0][1]
        assert np.allclose(other[2], blocks[0][2])
        assert other[3] == pytest.approx(blocks[0][3])
    # a block of arrivals at the stated rate lasts block / rate
    rate = t["arrivals"]["rate_per_s"]
    assert blocks[0][3] == pytest.approx(t["block"] / rate, rel=0.02)


def test_until_returns_the_requests_due_in_the_horizon():
    t = load("doc_prefill_stratified")
    reqs = T.RequestSource(t, 9, 40478, 512).until(20.0)
    assert all(r["due_s"] < 20.0 for r in reqs)
    assert [r["index"] for r in reqs] == list(range(len(reqs)))
    assert len(reqs) == pytest.approx(20.0 * t["arrivals"]["rate_per_s"],
                                      rel=0.1)


def test_arrivals_are_stratified_and_the_file_says_so():
    """What the mix is named for: neighbouring gaps vary as an exponential
    distribution's do, but the number of requests due in a window is all
    but the same for every seed, which independent draws would not give."""
    t = load("doc_prefill_stratified")
    assert "stratified" in t["arrivals"]["_gaps"]
    rate, counts, cvs = t["arrivals"]["rate_per_s"], [], []
    for seed in range(20):
        reqs = T.RequestSource(t, seed, 40478, 512).until(40.0)
        counts.append(len(reqs))
        gaps = np.diff([r["due_s"] for r in reqs])
        cvs.append(gaps.std() / gaps.mean())
    assert 0.9 < min(cvs) and max(cvs) < 1.1     # exponential: 1
    # a Poisson count at this rate would scatter by sqrt(1400) = 37
    assert np.std(counts) < 0.5 * np.sqrt(rate * 40.0)
    assert np.mean(counts) == pytest.approx(rate * 40.0, rel=0.02)


@pytest.mark.parametrize("chips", [1, 4])
def test_training_rows_all_differ_and_the_mask_rate_rises(chips):
    t = load("pretrain_s128")
    ring = T.train_ring(t, 2**31 + 5, 30522, chips)
    assert len(ring) == t["ring"]
    ids, labels = ring[0]
    assert ids.shape == (t["rows_per_chip"] * chips, t["seq_len"])
    assert len({row.tobytes() for row in ids}) == len(ids)
    assert ids.max() < 30522 and ids.min() >= 0
    labelled = (labels >= 0).mean(axis=1)
    n = len(ids)
    assert labelled[: n // 4].mean() < 0.12 < 0.18 < labelled[-n // 4:].mean()
    assert (labels >= 0).mean() == pytest.approx(0.15, abs=0.01)
    assert (ids[labels >= 0] == t["mask_token"]).all()
    again = T.train_ring(t, 2**31 + 5, 30522, chips)
    assert np.array_equal(again[3][0], ring[3][0])
    assert not np.array_equal(T.train_ring(t, 6, 30522, chips)[0][0], ids)
