"""The load generator shared by the closed- and open-loop HTTP drivers: one
process with the server (a chip has one owner), a fixed pool of worker
threads that each own one keep-alive HTTP/1.1 connection, so no run piles up
sockets in TIME_WAIT. A worker only sends, parses chunks and takes
timestamps. A request that fails (429/503/504, a timeout, a broken stream)
is a record with `failed` set, never an exception."""
import http.client
import json
import queue
import socket
import threading
import time

import jax

from benchmark import stats, trace


class Worker(threading.Thread):
    def __init__(self, load, n):
        super().__init__(daemon=True, name="bench-client-%d" % n)
        self.load, self.conn = load, None

    def _connect(self):
        sut = self.load.sut
        self.conn = http.client.HTTPConnection(
            sut.host, sut.port, timeout=self.load.timeout_s)
        self.conn.connect()
        self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _drop(self):
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None

    def perform(self, req, due):
        """One streaming request; returns its record."""
        rec = {"index": req["index"], "due": due, "prompt": req["prompt"],
               "max_new": req["max_new"],
               "bucket": bucket_of(len(req["prompt"]),
                                   self.load.run.traffic["prompt_buckets"]),
               "token_times": [], "tokens": [], "done": False,
               "failed": False, "cut": False, "status": None, "error": None}
        body = json.dumps({"prompt": req["prompt"].tolist(),
                           "max_new_tokens": req["max_new"]}).encode()
        try:
            with jax.profiler.TraceAnnotation("bench.client_send"):
                if self.conn is None:
                    self._connect()
                rec["t_send"] = time.monotonic()
                self.conn.request("POST", self.load.sut.path, body=body,
                                  headers={"Content-Type": "application/json"})
            with jax.profiler.TraceAnnotation("bench.client_wait"):
                resp = self.conn.getresponse()
                rec["status"] = resp.status
                if resp.status != 200:
                    resp.read()
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


class OpenWorker(Worker):
    """Takes requests as the scheduler releases them."""

    def run(self):
        while True:
            item = self.load.work.get()
            if item is None:
                return
            self.load.records.append(self.perform(*item))


class ClosedWorker(Worker):
    """A caller that waits for its reply, then sends its next request."""

    def run(self):
        load = self.load
        while time.monotonic() < load.t_stop and not load.abort.is_set():
            req = load.source.next()
            load.records.append(self.perform(req, time.monotonic()))


class Load:
    """One measured window of HTTP load against `sut`."""

    def __init__(self, run, sut, source, n_workers, worker_cls):
        self.run, self.sut, self.source = run, sut, source
        self.timeout_s = float(run.traffic.get("client_timeout_s", 30))
        self.abort = threading.Event()
        self.work = queue.Queue()
        self.records = []            # list.append is atomic
        self.gauges = []
        self.workers = [worker_cls(self, i) for i in range(n_workers)]
        self.t0 = self.t_end = None

    def _sample_gauges(self):
        while not self.abort.is_set():
            g = self.sut.gauges()
            g["t"] = time.monotonic()
            self.gauges.append(g)
            time.sleep(0.05)

    def _schedule(self, requests):
        """Open loop: release each request at its due time, whatever the
        server is doing."""
        for req in requests:
            due = self.t_start + req["due_s"]
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            if self.abort.is_set():
                return
            self.work.put((req, due))

    def go(self, seconds, requests=None):
        """Offer load for `ramp_s`, then for the window. In a traced run the
        window the host-side readers see is the first 0.6 of `seconds`; the
        profiler then runs for three seconds under the same load, so that
        what starting, stopping and writing a trace costs the host does not
        pass for the server's queueing."""
        run = self.run
        ramp = float(run.traffic.get("ramp_s", 0))
        window = 0.6 * seconds if run.trace else seconds
        capture = None
        if run.trace:
            capture = trace.Capture(run.out_dir, ramp + window,
                                    min(3.0, 0.3 * seconds))
        self.t_start = time.monotonic()
        self.t0 = run.obs["window_t0"] = self.t_start + ramp
        self.t_end = self.t0 + window
        self.t_stop = self.t_end + (capture.seconds + 0.5 if capture else 0)
        sampler = threading.Thread(target=self._sample_gauges, daemon=True)
        sampler.start()
        if capture is not None:
            capture.start()
        for w in self.workers:
            w.start()
        if requests is not None:
            threading.Thread(target=self._schedule, args=(requests,),
                             daemon=True).start()
        time.sleep(max(0.0, self.t0 - time.monotonic()))
        counters0 = self.sut.counters()
        time.sleep(max(0.0, self.t_end - time.monotonic()))
        counters1 = self.sut.counters()
        time.sleep(max(0.0, self.t_stop - time.monotonic()))
        # the window is over: let short requests finish, then cut the rest
        drain_until = time.monotonic() + float(run.traffic.get("drain_s", 0))
        while time.monotonic() < drain_until and (
                self.sut.live_slots() > 0 or not self.work.empty()):
            time.sleep(0.05)
        self.abort.set()
        while True:   # due in the window and never sent: missed
            try:
                req, due = self.work.get_nowait()
            except queue.Empty:
                break
            self.records.append({
                "index": req["index"], "due": due, "prompt": req["prompt"],
                "token_times": [], "tokens": [], "done": False,
                "failed": True, "cut": False, "status": None,
                "error": "not sent before the window closed"})
        for _ in self.workers:
            self.work.put(None)
        deadline = time.monotonic() + 10
        for w in self.workers:
            w.join(max(0.0, deadline - time.monotonic()))
        while self.sut.live_slots() > 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        stuck = sum(w.is_alive() for w in self.workers)
        if stuck:
            run.note("%d client threads did not end; left behind" % stuck)
        if capture is not None:
            run.obs["trace"] = capture.finish()
            if capture.error:
                run.note("trace: " + capture.error)
        return self._reduce(counters0, counters1)

    def _reduce(self, counters0, counters1):
        """Records -> observations and the end-to-end metrics. Every rate
        is over the whole window, every tail over all its samples."""
        run, t0, t_end = self.run, self.t0, self.t_end
        window_s = t_end - t0
        everything = list(self.records)
        recs = [r for r in everything if t0 <= r["due"] < t_end]
        failed = [r for r in recs if r["failed"]]
        ttft, late, gaps, tokens_in, live_row_s = [], [], [], 0, 0.0
        for r in everything:
            tt = r["token_times"]
            if t0 <= r["due"] < t_end:
                if "t_send" in r:
                    late.append(r["t_send"] - r["due"])
                if tt and not r["failed"]:
                    ttft.append(tt[0] - r["due"])
            tokens_in += sum(1 for t in tt if t0 <= t <= t_end)
            for j, (a, b) in enumerate(zip(tt, tt[1:])):
                if t0 <= b <= t_end:
                    gaps.append(b - a)
                    live_row_s += (b - a) * (len(r["prompt"]) + j + 1)
        # a request that failed, or that never got a first token though it
        # was due well inside the window, misses every latency limit
        missed = len(failed) + sum(
            1 for r in recs if not r["failed"] and not r["token_times"]
            and not r["cut"])
        finished = [r for r in self.records if r["done"]]
        for r in failed[:5]:
            run.note("failed request %d: status %s %s"
                     % (r["index"], r["status"], r["error"]))
        run.obs.update(
            attempted=len(recs), failed=len(failed), window_s=window_s,
            tokens_received=tokens_in, ttft_s=ttft, gaps_s=gaps,
            late_s=late, finished=finished, live_row_seconds=live_row_s,
            counters={k: counters1[k] - counters0[k] for k in counters1
                      if isinstance(counters1[k], (int, float))
                      and not isinstance(counters1[k], bool)},
            gauges=[g for g in self.gauges if t0 <= g["t"] <= t_end],
            prompt_lens=[len(r["prompt"]) for r in recs])
        run.note("%d requests due, %d failed, %d finished, %d tokens in "
                 "%.2f s; longest gap between two tokens of a stream %.3f s"
                 % (len(recs), len(failed), len(finished), tokens_in,
                    window_s, max(gaps, default=0)))
        return {"serve_tokens_per_s": stats.rate(tokens_in, window_s),
                "itl_ms_p90": stats.tail_ms(gaps, 90),
                "ttft_ms_p90": stats.tail_ms(ttft, 90, missed=missed)}


def bucket_of(plen, buckets):
    return min(b for b in buckets if b >= plen)


def source_for(run, sut):
    from benchmark import traffic as traffic_mod

    return traffic_mod.RequestSource(
        run.traffic, run.seed, sut.model["vocab_size"],
        sut.serving["cache_len"])


def warm(run, sut):
    """One request through every prompt bucket over HTTP, so that every
    program, the slot write and the whole served path have run once."""
    import numpy as np

    worker = Worker(Load(run, sut, None, 0, Worker), -1)
    shortest = 1
    for b in sorted(run.traffic["prompt_buckets"]):
        req = {"index": -b, "prompt": np.ones(shortest, np.int64),
               "max_new": 2}
        rec = worker.perform(req, time.monotonic())
        if not rec["done"]:
            raise RuntimeError("warm-up request through bucket %d failed: "
                               "%s %s" % (b, rec["status"], rec["error"]))
        shortest = b + 1
    worker._drop()
