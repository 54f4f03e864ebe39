"""The harness: finds a cell's configuration, traffic mix, system, driver and
per-layer readers by the names in BENCHMARK.json, runs them, and prints the
result line. It knows no cell, model or metric by name: a later PR adds
files and manifest entries and edits nothing here (README.md says which).
"""
import argparse
import copy
import importlib
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
EXIT_NO_DEVICE, EXIT_MANIFEST, EXIT_COMPILED_IN_WINDOW = 2, 3, 4


class Refuse(SystemExit):
    """Something wrong on every run: say it on stderr, print no result."""

    def __init__(self, code, message):
        print("benchmark: " + message, file=sys.stderr, flush=True)
        super().__init__(code)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    try:
        return load_json(os.path.join(root, "BENCHMARK.json"))
    except (OSError, ValueError) as e:
        raise Refuse(EXIT_MANIFEST, "cannot read BENCHMARK.json: %s" % e)


def overlay(base, over):
    """`base` with the groups of `over` laid over it, one level deep."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k].update(v)
        else:
            out[k] = v
    return out


def resolve_cell(manifest, workload, root=ROOT, rehearse=False):
    """-> (cell, config, traffic): the manifest's entry and the two data
    files it names, with their `rehearsal` groups laid over in a rehearsal."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise Refuse(EXIT_MANIFEST, "no workload %r in BENCHMARK.json (has %s)"
                     % (workload, sorted(cells)))
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    try:
        config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
        traffic = load_json(os.path.join(
            root, os.path.dirname(os.path.dirname(
                configs[cell["config"]]["file"])),
            "traffic", cell["traffic"] + ".json"))
    except (KeyError, OSError, ValueError) as e:
        raise Refuse(EXIT_MANIFEST, "cell %r: %s: %s"
                     % (workload, type(e).__name__, e))
    if rehearse:
        config = overlay(config, config.get("rehearsal", {}))
        traffic = overlay(traffic, traffic.get("rehearsal", {}))
    # the model's own sizes are the file's top-level values, under the
    # keys of the source's config.json
    config["model"] = {k: v for k, v in config.items()
                       if not isinstance(v, (dict, list))}
    return cell, config, traffic


def apply_environment(config):
    """Settings of the system under test that the configuration states
    (keys that start with "_" are the file's own remarks)."""
    os.environ.update({k: v for k, v in config.get("environment", {}).items()
                       if not k.startswith("_")})


def metrics_for(manifest, group, workload):
    """The metrics of `group` this cell reports: those that list it under
    `workloads`, and those that list nothing."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def load_part(kind, name):
    """benchmark/<kind>/<name>.py, found by the name a data file gives."""
    try:
        return importlib.import_module("benchmark.%s.%s" % (kind, name))
    except ModuleNotFoundError as e:
        if e.name != "benchmark.%s.%s" % (kind, name):
            raise
        raise Refuse(EXIT_MANIFEST, "no benchmark/%s/%s.py" % (kind, name))


def find_device(chips, rehearse):
    """The device as jax reports it, and its row of the table of peaks.
    No TPU, too few chips or an unknown device_kind: exit, print nothing."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    table = load_json(os.path.join(HERE, "peaks.json"))
    if rehearse:
        if dev["platform"] != "cpu":
            raise Refuse(EXIT_NO_DEVICE, "--rehearse-cpu wants "
                         "JAX_PLATFORMS=cpu, found %s" % dev["platform"])
        if dev["count"] < chips:
            raise Refuse(EXIT_NO_DEVICE, "the cell asks for %d devices; set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=%d"
                         % (chips, chips))
        return dev, table["TPU v5 lite"]
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise Refuse(EXIT_NO_DEVICE, "the cell asks for %d TPU chip(s); jax "
                     "found %s" % (chips, devs))
    if dev["kind"] not in table:
        raise Refuse(EXIT_NO_DEVICE, "device_kind %r is not in "
                     "benchmark/peaks.json" % dev["kind"])
    return dev, table[dev["kind"]]


class CompileCounter:
    """Counts what compiles: every compile jax asks its persistent cache
    for (`requests`), those the cache held (`hits`), those it was given to
    keep (`misses`: jax keeps only what took a second or more to compile),
    and the program's own compile_start events."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax.monitoring

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self):
        from paddle_tpu import observability as obs

        return dict(self.counts, program=len(
            obs.get_recorder().of("compile_start")))

    @staticmethod
    def delta(a, b):
        return {k: b[k] - a[k] for k in a}


class Run:
    """Everything one run knows; systems, drivers and readers take it."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.obs = {}          # what the window observed, for the readers
        self.compared = {}     # name -> {"value", "limit"}
        self.notes = []
        self.marks = []

    def mark(self, label):
        """Remember how far into the process `label` was reached."""
        self.marks.append((label, round(time.monotonic() - self.t0, 2)))

    def note(self, text):
        self.notes.append(text)
        print("[bench] " + text, file=sys.stderr, flush=True)


class MemorySampler:
    """The fullest chip's memory while the window runs. The TPU runtime
    counts live arrays under `bytes_in_use` and the scratch of the loaded
    programs under `bytes_reserved`; its two `peak_` counters may come from
    different moments (set-up's peak of arrays, a later program's scratch),
    so their sum is not a peak. This reads both at the same instant, twice
    a second on a thread of its own and once more when the window has
    closed, and keeps the largest sum of one reading: a peak that was
    really held, at worst missed between two readings."""

    def __init__(self, chips, every_s=0.5):
        import jax

        self.devices = jax.local_devices()[:chips]
        self.every_s = every_s
        self.peak = {"bytes": 0, "in_use": 0, "reserved": 0, "readings": 0}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-memory")

    def read(self):
        self.peak["readings"] += 1
        for d in self.devices:
            stats = d.memory_stats() or {}
            in_use = int(stats.get("bytes_in_use", 0))
            reserved = int(stats.get("bytes_reserved", 0))
            if in_use + reserved > self.peak["bytes"]:
                self.peak.update(bytes=in_use + reserved, in_use=in_use,
                                 reserved=reserved)

    def _loop(self):
        while not self._stop.wait(self.every_s):
            self.read()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.read()


def _memory_stats():
    import jax

    return {k: v for k, v in (jax.local_devices()[0].memory_stats()
                              or {}).items() if "bytes" in k}


def bounded(fn, seconds, what, run):
    """Run `fn` on a thread for at most `seconds`; whatever it raises or
    however long it hangs, the run goes on."""
    box = {}

    def target():
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — shutdown cannot fail a run
            box["error"] = "%s: %s" % (type(e).__name__, e)

    t = threading.Thread(target=target, daemon=True, name="bench-" + what)
    t.start()
    t.join(seconds)
    if t.is_alive():
        run.note("%s did not end in %d s; left behind" % (what, seconds))
    elif "error" in box:
        run.note("%s raised %s; ignored" % (what, box["error"]))


def result_line(run, metrics, device, correct, breakdown=None):
    """The one JSON object of the contract. `compared` comes last."""
    line = {"correct": bool(correct),
            "attempted": int(run.obs.get("attempted", 0)),
            "failed": int(run.obs.get("failed", 0)),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["workload"] = run.cell["name"]
    line["seed"] = run.seed
    line["compared"] = run.compared
    return line


def read_per_layer(run, manifest):
    out = {}
    for m in metrics_for(manifest, "per_layer", run.cell["name"]):
        value = load_part("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def measure(run, device):
    """Everything of a run after the look for a chip: build, warm up,
    measure, read the memory, free the system, reduce, check. Returns the
    result line."""
    manifest, cell, config, traffic = (run.manifest, run.cell, run.config,
                                       run.traffic)
    t0 = run.t0
    system = load_part("systems", config["system"])
    driver = load_part("drivers", traffic["driver"])
    run.mark("imports+device")
    sut = system.build(run)
    run.mark("build")
    driver.warm(run, sut)
    run.mark("warm")
    before = run.compiles.snapshot()
    run.obs["setup_compiles"] = before
    run.note("set-up reached (s): %s; compiles in set-up: %s"
             % (run.marks, before))
    with MemorySampler(run.chips) as memory:
        end_to_end = driver.window(run, sut)     # the measured window
    # set-up ends where the driver opened its window (after any ramp)
    run.obs["setup_s"] = run.obs["window_t0"] - t0
    in_window = CompileCounter.delta(before, run.compiles.snapshot())
    end_to_end["setup_s"] = run.obs["setup_s"]
    run.note("compiles inside the window: %s; fullest reading of the "
             "memory in it: %s; the runtime's counters after it: %s"
             % (in_window, memory.peak, _memory_stats()))
    device["memory_peak_bytes"] = memory.peak["bytes"]
    run.obs["memory_peak_bytes"] = device["memory_peak_bytes"]
    bounded(sut.close, 30, "closing the system under test", run)
    if any(in_window.values()):
        raise Refuse(EXIT_COMPILED_IN_WINDOW, "something compiled inside the "
                     "measured window: %s; warm it up in set-up" % in_window)

    if run.trace:
        tr = run.obs.get("trace")
        if tr and tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
        metrics = read_per_layer(run, manifest)
        from benchmark import trace as trace_mod
        breakdown = tr and {
            "device_ops": trace_mod.top(tr.get("ops", {})),
            "idle_gaps": trace_mod.top(tr.get("idle_gaps", {}))}
    else:
        wanted = metrics_for(manifest, "end_to_end", cell["name"])
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in wanted if end_to_end.get(m["name"]) is not None}
        breakdown = None

    t_check, c0 = time.monotonic(), run.compiles.snapshot()
    system.check(run, sut)                       # the plain reference
    run.note("reference check took %.1f s; its compiles: %s; memory: %s" % (
        time.monotonic() - t_check,
        CompileCounter.delta(c0, run.compiles.snapshot()),
        _memory_stats()))
    correct = bool(run.compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in run.compared.values())
    return result_line(run, metrics, device, correct, breakdown)


def prepare(workload, rehearse, t0):
    """What every entry point does before it builds anything: resolve the
    cell, apply the configuration's settings, find the device, place the XLA
    cache. -> (new_run, device, cache_dir); `new_run(seed, seconds, ...)`
    makes the Run of one window of that cell."""
    manifest = load_manifest()
    cell, config, traffic = resolve_cell(manifest, workload,
                                         rehearse=rehearse)
    apply_environment(config)
    try:
        import paddle_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        raise Refuse(EXIT_MANIFEST, "the system under test is not in this "
                     "checkout: %s" % e)
    from paddle_tpu.fluid import compile_cache

    device, peaks = find_device(cell["chips"], rehearse)
    cache_dir = compile_cache.configure_xla_cache()

    def new_run(seed, seconds, trace=False, compiles=None, traffic=traffic):
        return Run(manifest=manifest, cell=cell, config=config,
                   traffic=traffic, seed=seed, seconds=seconds, trace=trace,
                   chips=cell["chips"], peaks=peaks, rehearse=rehearse,
                   out_dir=os.path.join(ROOT, ".bench_runs", cell["name"]),
                   t0=t0, compiles=compiles)

    return new_run, device, cache_dir


def main(argv, t0):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    new_run, device, cache_dir = prepare(args.workload, args.rehearse_cpu, t0)
    run = new_run(args.seed, args.seconds, trace=bool(args.trace),
                  compiles=CompileCounter())
    cell, out_dir = run.cell, run.out_dir
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    if run.rehearse:
        print("REHEARSAL on the CPU at tiny sizes: not a device result",
              flush=True)
    run.note("cell %s seed %d seconds %g trace %d on %s; xla cache %s"
             % (cell["name"], run.seed, run.seconds, args.trace, device,
                cache_dir))

    line = measure(run, device)
    for name, c in run.compared.items():
        print("compared %s = %r limit %r %s" % (
            name, c["value"], c["limit"],
            "ok" if c["value"] is not None and c["value"] <= c["limit"]
            else "NOT CORRECT"), file=sys.stderr)
    sys.stderr.flush()
    if run.rehearse:
        print(json.dumps({"rehearsal": True, "platform": device["platform"],
                          "would_print": line}))
    else:
        print(json.dumps(line))
    sys.stdout.flush()
    shutil.rmtree(out_dir, ignore_errors=True)
    # the result stands; nothing in interpreter or runtime teardown (server
    # and engine threads are daemons, closed above) may change the exit code
    os._exit(0)
