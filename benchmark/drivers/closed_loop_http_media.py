"""Driver `closed_loop_http_media`: `clients` callers that each wait for their
reply before they send their next request, each request carrying page images
beside its token ids (`benchmark/traffic_media.py`): the body's `images` are
raw pixels in base64, made from the request's (h, w, seed) where it is sent.
The window is `_http.Load`'s; a record holds every key its `_reduce` reads,
and `images`."""
import http.client
import json
import time

import jax
import numpy as np

from benchmark import traffic_media
from benchmark.drivers import _http


class MediaWorker(_http.ClosedWorker):
    def perform(self, req, due):
        """One streaming request with images; returns its record."""
        rec = {"index": req["index"], "due": due, "prompt": req["prompt"],
               "max_new": req["max_new"], "images": req["images"],
               "bucket": _http.bucket_of(
                   len(req["prompt"]),
                   self.load.run.traffic["prompt_buckets"]),
               "token_times": [], "tokens": [], "done": False,
               "failed": False, "cut": False, "status": None, "error": None}
        body = b'{"prompt": %s, "max_new_tokens": %d, "images": %s}' % (
            json.dumps(req["prompt"].tolist()).encode(), req["max_new"],
            traffic_media.images_field(req["images"], self.load.sut.patch))
        try:
            with jax.profiler.TraceAnnotation("bench.client_send"):
                if self.conn is None:
                    self._connect()
                rec["t_send"] = time.monotonic()
                self.conn.request("POST", self.load.sut.path, body=body,
                                  headers={"Content-Type": "application/json"})
            del body
            with jax.profiler.TraceAnnotation("bench.client_wait"):
                resp = self.conn.getresponse()
                rec["status"] = resp.status
                if resp.status != 200:
                    rec["error"] = resp.read()[:300].decode("utf-8", "replace")
                    rec["failed"] = True
                    return rec
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    t = time.monotonic()
                    doc = json.loads(line)
                    if "token" in doc:
                        rec["token_times"].append(t)
                        rec["tokens"].append(int(doc["token"]))
                    elif doc.get("done"):
                        rec["done"] = doc.get("finish_reason") == "length"
                        rec["failed"] = not rec["done"]
                        rec["error"] = doc.get("error")
                    if self.load.abort.is_set() and not rec["done"]:
                        rec["cut"] = True     # the window is over
                        self._drop()
                        return rec
                if not rec["done"] and not rec["failed"]:
                    rec["failed"] = True
                    rec["error"] = "stream ended without a done line"
        except (OSError, http.client.HTTPException, ValueError) as e:
            if self.load.abort.is_set():
                rec["cut"] = True
            else:
                rec["failed"] = True
                rec["error"] = "%s: %s" % (type(e).__name__, e)
            self._drop()
        return rec


def source_for(run, sut):
    return traffic_media.MediaSource(
        run.traffic, run.seed, sut.model["vocab_size"],
        sut.serving["cache_len"], sut.model["media_placeholder_token_id"])


def grid_for(bucket, below, side):
    """The even grid (h, w), sides at most `side`, of most patches within
    (below, bucket]."""
    fits = [(h * w, h, w) for h in range(2, side + 1, 2)
            for w in range(h, side + 1, 2) if below < h * w <= bucket]
    return max(fits)[1:]


def warm(run, sut):
    """One request through every prompt bucket AND every patch bucket over
    HTTP, so that every program (the towers, the fills), the write of an
    image's rows and the whole served path have run once; nothing compiles
    in a window."""
    worker = MediaWorker(_http.Load(run, sut, None, 0, MediaWorker), -1)
    media_id = sut.model["media_placeholder_token_id"]
    prompts = sorted(run.traffic["prompt_buckets"])
    towers, below, grids = sorted(sut.patch_buckets), 0, []
    for b in towers:
        grids.append(grid_for(b, below, sut.table_side))
        below = b
    # the patch buckets dealt over the prompt buckets, the last takes the rest
    shortest = 1
    for j, b in enumerate(prompts):
        mine = grids[j:j + 1] if j < len(prompts) - 1 else grids[j:]
        rows = [h * w // 4 for h, w in mine]
        plen = max(shortest, sum(rows) + 2 * (len(mine) + 1))
        prompt, at = np.ones(plen, np.int64), 2
        for n_rows in rows:
            prompt[at:at + n_rows] = media_id
            at += n_rows + 2
        req = {"index": -b, "prompt": prompt, "max_new": 2,
               "images": [(h, w, 7 + h + w) for h, w in mine]}
        rec = worker.perform(req, time.monotonic())
        if not rec["done"]:
            raise RuntimeError("warm-up request through bucket %d failed: "
                               "%s %s" % (b, rec["status"], rec["error"]))
        shortest = b + 1
    worker._drop()


def window(run, sut):
    load = _http.Load(run, sut, source_for(run, sut),
                      int(run.traffic["clients"]), MediaWorker)
    return load.go(run.seconds)
