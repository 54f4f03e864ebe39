"""Traffic that is not token ids alone: requests that carry page images. A
source over `traffic.RequestSource` that, from the same seed, gives each
request of a block its images and writes their placeholder runs into its
prompt. A mix is a data file of parameters under benchmark/traffic/ (its
`images` group); nothing here knows a mix by name.

Total over seeds: every request is valid by construction (what
`RequestSource` promises; the placeholder id occurs exactly once for every
media row, an id drawn equal to it is moved by one; the first run follows at
least `lead_ids` ids and every run is followed by one; a prompt too short
for its images' rows is lengthened to hold them, never past the largest
bucket). Steady over seeds: every block holds the same set of image counts
and the same sets of grid heights and widths (evenly spaced even numbers of
patches), in an order drawn from the seed. The prompt's length COUNTS its
media rows.

Pixels are made where they are sent, from (h, w, seed) alone (`pixels`), so
that a block of requests holds a few numbers an image and the check can make
the same image again."""
import base64

import numpy as np

from benchmark import traffic


def pixels(h, w, seed, patch=14):
    """The raw image of a grid of h x w patches: uint8 (patch h, patch w,
    3), from `seed` alone."""
    return np.random.default_rng(int(seed)).integers(
        0, 256, (patch * h, patch * w, 3), dtype=np.uint8)


def images_field(images, patch=14):
    """The `images` list of a `:generate` body as JSON bytes, pixels raw in
    base64; assembled from bytes (no `json.dumps` over megabytes)."""
    parts = [b'{"grid": [%d, %d], "pixels": "%s"}' % (
        h, w, base64.b64encode(pixels(h, w, seed, patch).tobytes()))
        for h, w, seed in images]
    return b"[" + b", ".join(parts) + b"]"


class MediaSource(traffic.RequestSource):
    """`RequestSource` whose requests also hold `images`: a list of (h, w,
    seed) with h, w even numbers of patches; the prompt holds `media_id`
    once for every media row (h w / 4 an image), as runs between runs of
    text."""

    def __init__(self, traffic_mix, seed, vocab_size, cache_len, media_id):
        super().__init__(traffic_mix, seed, vocab_size, cache_len)
        self.media_id = int(media_id)
        self.im = traffic_mix["images"]

    def _make_block(self):
        super()._make_block()
        n, rng, im = self.block, self.rng, self.im
        counts = rng.permutation(np.resize(np.asarray(im["counts"]), n))
        total = int(counts.sum())
        lo, hi = im["side_patches"]["min"] // 2, im["side_patches"]["max"] // 2
        hs = 2 * rng.permutation(traffic._spaced(lo, hi, total))
        ws = 2 * rng.permutation(traffic._spaced(lo, hi, total))
        seeds = rng.integers(0, 2 ** 31, total)
        lead, at = int(im.get("lead_ids", 16)), 0
        for req, k in zip(self._buf[-n:], counts):
            grids = [(int(hs[at + j]), int(ws[at + j]), int(seeds[at + j]))
                     for j in range(int(k))]
            at += int(k)
            self._place(req, grids, lead)

    def _place(self, req, grids, lead):
        rows = [h * w // 4 for h, w, _ in grids]
        need = sum(rows) + lead * (len(grids) + 1)
        prompt = req["prompt"]
        if len(prompt) < need:       # too short for its images: lengthened
            extra = self.rng.integers(1, self.vocab, need - len(prompt))
            prompt = np.concatenate([prompt, extra.astype(np.int64)])
            req["max_new"] = int(min(req["max_new"],
                                     self.cache_len - len(prompt) + 1))
        prompt = np.where(prompt == self.media_id, prompt - 1, prompt)
        # the text in len(grids) + 1 runs of at least `lead` ids each, cut
        # at places drawn from the seed; a run of placeholders after each
        # but the last
        spare = len(prompt) - need
        cuts = np.sort(self.rng.integers(0, spare + 1, len(grids)))
        text = np.diff(np.concatenate([[0], cuts, [spare]])) + lead
        at = 0
        for run, n_rows in zip(text[:-1], rows):
            at += int(run)
            prompt[at:at + n_rows] = self.media_id
            at += n_rows
        req["prompt"], req["images"] = prompt, grids
