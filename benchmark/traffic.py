"""The one general traffic generator. A traffic mix is a data file of
parameters under benchmark/traffic/; nothing here knows a mix by name.

Total over seeds: for every whole-number seed every request is valid by
construction (ids < vocabulary, prompt <= the largest bucket, prompt +
max_new - 1 <= cache_len). Steady over seeds: requests come in blocks, and
every block holds the same set of prompt lengths, output lengths and
inter-arrival gaps (the quantiles of the stated distribution), in an order
drawn from the seed. So two seeds offer the same work in another order, and
the number of requests in a window hardly depends on the seed.

That makes the arrivals stratified, not a Poisson process: the gaps of a
block are the quantiles of the exponential distribution, shuffled, so gaps
next to each other are as irregular as a Poisson process's, but every block
lasts block / rate seconds and no excursion of the rate outlives a block. A
tail measured under it is lower than under independent draws at the same
mean rate.
"""
import threading

import numpy as np


def _spaced(lo, hi, n):
    """n whole numbers spread evenly over [lo, hi]."""
    return np.round(np.linspace(lo, hi, n)).astype(np.int64)


class RequestSource:
    """Requests in order, made block by block from the seed; safe to call
    from several client threads. Each request is a dict with `index`,
    `prompt` (int64 array), `max_new` and, for open-loop mixes, `due_s`
    (seconds after the window opens)."""

    def __init__(self, traffic, seed, vocab_size, cache_len):
        self.t = traffic
        self.rng = np.random.default_rng(int(seed))
        self.vocab = int(vocab_size)
        self.cache_len = int(cache_len)
        self.block = int(traffic.get("block", 256))
        self.max_prompt = min(max(traffic["prompt_buckets"]), self.cache_len)
        self._lock = threading.Lock()
        self._buf, self._made, self._due = [], 0, 0.0

    def _make_block(self):
        n, t, rng = self.block, self.t, self.rng
        plens = rng.permutation(_spaced(
            t["prompt_tokens"]["min"], t["prompt_tokens"]["max"], n))
        news = rng.permutation(_spaced(
            t["max_new_tokens"]["min"], t["max_new_tokens"]["max"], n))
        a = t.get("arrivals")
        gaps = None
        if a:
            # the quantiles of the exponential distribution, the same set
            # in every block, shuffled
            gaps = rng.permutation(
                -np.log(1.0 - (np.arange(n) + 0.5) / n)
                / float(a["rate_per_s"]))
        for i in range(n):
            plen = int(min(max(plens[i], 1), self.max_prompt))
            new = int(min(max(news[i], 1), self.cache_len - plen + 1))
            prompt = rng.integers(1, self.vocab, plen)
            req = {"index": self._made, "prompt": prompt.astype(np.int64),
                   "max_new": new}
            if gaps is not None:
                self._due += float(gaps[i])
                req["due_s"] = self._due
            self._made += 1
            self._buf.append(req)

    def next(self):
        with self._lock:
            if not self._buf:
                self._make_block()
            return self._buf.pop(0)

    def until(self, horizon_s):
        """All open-loop requests due before `horizon_s`."""
        out = []
        while True:
            req = self.next()
            if req["due_s"] >= horizon_s:
                return out
            out.append(req)


def train_ring(traffic, seed, vocab_size, chips):
    """The ring of host batches of a training mix: `ring` batches of
    rows_per_chip * chips rows, every row different. 15% of the positions
    are labelled on average, at a rate that rises from the first row to
    the last, so any part of a batch left out (a chip's share too) moves
    the loss. Labelled positions hold the
    mask token in the input; the others carry the label -1 (ignored)."""
    rng = np.random.default_rng(int(seed))
    rows = int(traffic["rows_per_chip"]) * int(chips)
    seq = int(traffic["seq_len"])
    lo, hi = traffic["mask_rate"]["min"], traffic["mask_rate"]["max"]
    ring = []
    for _ in range(int(traffic["ring"])):
        ids = rng.integers(min(1000, vocab_size // 2), vocab_size,
                           (rows, seq), dtype=np.int64)
        rate = np.linspace(lo, hi, rows)[:, None]
        mask = rng.random((rows, seq)) < rate
        labels = np.where(mask, ids, -1).astype(np.int64)
        ids = np.where(mask, int(traffic.get("mask_token", 103)), ids)
        ring.append((ids, labels))
    return ring
