"""From the profiler's trace to numbers. `capture` runs the jax profiler
for a few seconds inside the window, `rows_from_xplane` flattens what it
wrote to plain rows, and `reduce` turns rows into the quantities the
per-layer readers and the result line need. `reduce` is checked against
the small recorded trace in testdata/.

A row is (plane, line, name, start_ns, duration_ns). Device planes are named
"/device:TPU:<n>"; their "XLA Ops" line holds one event per executed
operation and "XLA Modules" one per executed program. The harness's own
`jax.profiler.TraceAnnotation`s (names starting with "bench.") appear on
the host's thread lines.
"""
import glob
import os
import re
import shutil
import threading
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANNOTATION = "bench."


def rows_from_xplane(path):
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(ANNOTATION):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return rows


def _union(intervals):
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def module_base(name):
    """"jit__fwd(1234567)" -> "jit__fwd": the program's name without the
    fingerprint XLA appends."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(rows, window_s):
    """-> dict with, per device plane, busy seconds (union of operation
    intervals); over all planes, per-module execution counts and seconds;
    per-operation seconds; idle gaps attributed to the host annotation
    that covered most of each. `window_s` is the traced window's length by
    the host's clock."""
    per_plane, modules, ops, annotations = {}, {}, {}, []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                per_plane.setdefault(plane, []).append((start, start + dur))
                ops[name] = ops.get(name, 0.0) + dur * 1e-9
            elif line == MODULES_LINE:
                m = modules.setdefault(module_base(name), {
                    "count": 0, "seconds": 0.0, "by_plane": {}})
                m["count"] += 1
                m["seconds"] += dur * 1e-9
                m["by_plane"][plane] = (m["by_plane"].get(plane, 0.0)
                                        + dur * 1e-9)
        elif name.startswith(ANNOTATION):
            annotations.append((start, start + dur, name[len(ANNOTATION):]))
    busy, merged = {}, {}
    for plane, iv in per_plane.items():
        merged[plane] = _union(iv)
        busy[plane] = sum(e - s for s, e in merged[plane]) * 1e-9
        # the profiler records a little beyond the host's start and stop:
        # the window is never shorter than what a device was seen to span
        window_s = max(window_s,
                       (merged[plane][-1][1] - merged[plane][0][0]) * 1e-9)
    out = {"window_s": window_s, "busy_s_by_plane": busy,
           "modules": modules, "ops": ops, "collective_s_by_plane": {}}
    if not busy:
        return out
    out["busy_s"] = sum(busy.values()) / len(busy)
    # collectives: operation time not overlapped by any other operation
    for plane in per_plane:
        coll = [(s, s + d) for p, l, n, s, d in rows
                if p == plane and l == OPS_LINE and is_collective(n)]
        rest = _union([(s, s + d) for p, l, n, s, d in rows
                       if p == plane and l == OPS_LINE
                       and not is_collective(n)])
        out["collective_s_by_plane"][plane] = sum(
            _uncovered(s, e, rest) for s, e in _union(coll)) * 1e-9
    # idle gaps of the fullest (busiest) device, by host annotation
    fullest = max(busy, key=busy.get)
    gaps = {}
    iv = merged[fullest]
    for (_, e0), (s1, _) in zip(iv, iv[1:]):
        label = _label(e0, s1, annotations)
        gaps[label] = gaps.get(label, 0.0) + (s1 - e0) * 1e-9
    out["idle_gaps"] = gaps
    return out


def is_collective(op_name):
    return bool(re.match(r"^%?(all-reduce|all-gather|reduce-scatter|"
                         r"all-to-all|collective-permute)", op_name))


def _uncovered(s, e, merged):
    """Length of [s, e) not covered by the merged intervals."""
    left = e - s
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        left -= min(e, me) - max(s, ms)
    return max(left, 0)


def _label(s, e, annotations):
    best, best_cover = "unannotated", 0
    for a_s, a_e, name in annotations:
        cover = min(e, a_e) - max(s, a_s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def top(table, n=10):
    """The n largest entries, for the result line's `breakdown`. The trace
    names a device operation by its whole HLO text: the tiling and layout
    annotations go, and the rest is cut to a line a reader can take in."""
    return [[re.sub(r"\{[^{}]*\}", "", k)[:200], v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


class Capture:
    """Traces `seconds` of the window, starting `after_s` into it, on a
    thread of its own; the trace directory lives under `out_dir` and is
    removed once reduced, whatever happens."""

    def __init__(self, out_dir, after_s, seconds):
        self.dir = os.path.join(out_dir, "trace")
        self.after_s, self.seconds = after_s, seconds
        self.result, self.error = None, None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace")

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            shutil.rmtree(self.dir, ignore_errors=True)
            time.sleep(self.after_s)
            jax.profiler.start_trace(self.dir)
            t0 = time.monotonic()
            time.sleep(self.seconds)
            window_s = time.monotonic() - t0
            jax.profiler.stop_trace()
            self.window_s = window_s
        except Exception as e:  # noqa: BLE001 — reported, not raised
            self.error = "%s: %s" % (type(e).__name__, e)

    def finish(self, timeout=120):
        """After the window: wait for the trace, reduce it, delete it."""
        self._thread.join(timeout)
        try:
            if self.error is None and not self._thread.is_alive():
                paths = glob.glob(os.path.join(
                    self.dir, "plugins", "profile", "*", "*.xplane.pb"))
                rows = []
                for p in paths:
                    rows.extend(rows_from_xplane(p))
                self.result = reduce(rows, self.window_s)
            elif self.error is None:
                self.error = "the profiler did not stop in %d s" % timeout
        except Exception as e:  # noqa: BLE001
            self.error = "%s: %s" % (type(e).__name__, e)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.result
