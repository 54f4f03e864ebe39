"""`solar_kda_scan_roofline_pct` (ISSUE 45): the Pallas kernel of the delta
rule's chunked scan (`kda_scan_fwd`) against the chip's roofline, read from
the adapter's own traced fill; against a hand count of its least seconds,
against fabricated traces, against a program that has no such kernel (the
parent's XLA form) and on the line of a traced rehearsal of its cell, whose
64-wide heads take the XLA form: the line leaves the metric out."""
import pytest

from conftest import ROOT

from benchmark import costs_solar, harness
from test_benchmark_harness import last_line, run_command

NAME = "solar_kda_scan_roofline_pct"
CELL = "solar_doc_context_decode"
M = harness.load_manifest(ROOT)
ENTRY = {m["name"]: m for m in M["per_layer"]}[NAME]
PEAKS = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
READER = harness.load_part("metrics", NAME)
KERNEL = ("%%kda_scan_fwd%s = (bf16[1,4096,8192]{2,1,0}, "
          "f32[1,64,128,128]{3,2,1,0}) custom-call(")


def published():
    _, config, traffic = harness.resolve_cell(M, CELL, root=ROOT)
    return costs_solar.sizes(config), config, traffic


def test_the_entry_is_the_kernels_trace_metric_of_solars_cell():
    assert ENTRY == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels, serving programs",
        "moves": "itl_ms_p90", "workloads": [CELL]}
    # appended behind everything PR 44 left, whose entries stand as they did
    names = [m["name"] for m in M["per_layer"]]
    assert names.index(NAME) > names.index("engine_fill_rows_chunked_pct")
    assert ENTRY["layer"] in {m["layer"] for m in M["per_layer"][:-1]}


def test_the_least_seconds_are_the_hand_count():
    """64 heads of 128 channels, chunks of 64: 8.9 MFLOP a head and chunk,
    six passes; 16,384 positions are 256 chunks: 0.876 TFLOP-equivalent =
    4.45 ms at 197 TFLOP/s, over the 1.61 GB of operands' 1.97 ms."""
    m, _, _ = published()
    assert costs_solar.kda_dims(m)[:2] == (64, 128)
    a_chunk = 5 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert a_chunk == 8912896
    flops = 6 * 64 * 256 * a_chunk
    moved = 16384 * 8192 * (2 + 2 + 2 + 2 + 4)
    assert flops / 197e12 > moved / 819e9
    assert READER.scan_min_seconds(m, 16384, PEAKS) == pytest.approx(
        flops / 197e12)
    assert READER.scan_min_seconds(m, 16384, PEAKS) == pytest.approx(
        4.447e-3, rel=1e-3)
    # a real length inside a chunk counts the whole chunk, and no more
    assert READER.scan_min_seconds(m, 16321, PEAKS) == \
        READER.scan_min_seconds(m, 16384, PEAKS)
    assert READER.scan_min_seconds(m, 16320, PEAKS) == pytest.approx(
        flops * 255 / 256 / 197e12)
    # a chip with slow memory is bound by the bytes
    slow = dict(PEAKS, hbm_bytes_per_s=100e9)
    assert READER.scan_min_seconds(m, 16384, slow) == pytest.approx(
        moved / 100e9)


def solar_run(make_run, ops, modules=None, plen=16384):
    run = make_run(CELL)
    _, run.config, run.traffic = published()
    if ops is not None:
        run.obs["solar_fill"] = {"plen": plen, "trace": {
            "ops": ops, "modules": modules if modules is not None else {
                "jit_fwd_prefill_16384": {"count": 1, "seconds": 0.43,
                                          "by_plane": {}}}}}
    return run


def test_reader_is_the_share_of_the_kernels_seconds(make_run):
    m, _, _ = published()
    least = READER.scan_min_seconds(m, 16384, PEAKS)
    # three layers x four runs of 4,096: twelve call sites of 24 ms
    ops = {KERNEL % (".%d" % i if i else ""): 0.024 for i in range(12)}
    ops["%fusion.586 = f32[64,64]{1,0} fusion("] = 0.1
    ops["%gmm.9 = bf16[32768,1280]{1,0} custom-call("] = 0.05
    ops["%flash_fwd.1 = bf16[64,16384,128]{2,1,0} custom-call("] = 0.09
    run = solar_run(make_run, ops)
    assert READER.read(run) == pytest.approx(100 * 3 * least / (12 * 0.024))
    assert 4 < READER.read(run) < 6
    # two whole executions of the program: twice the seconds, the same share
    twice = solar_run(make_run, {k: 2 * v for k, v in ops.items()}, {
        "jit_fwd_prefill_16384": {"count": 2, "seconds": 0.86,
                                  "by_plane": {}}})
    assert READER.read(twice) == pytest.approx(READER.read(run))
    # the window's trace is not read: the fill's alone
    run.obs["trace"] = {"ops": {KERNEL % ".77": 9.0}, "modules": {}}
    assert READER.read(run) == pytest.approx(100 * 3 * least / (12 * 0.024))


@pytest.mark.parametrize("ops, modules", [
    # the parent's program: the scans are XLA's loops, no such kernel
    ({"%while.278 = (s32[], f32[1,64,128,128]{3,2,1,0}) while(": 0.3,
      "%kda_scan_fwd_like.1 = f32[8]{0} fusion(": 0.1}, None),
    ({}, None),
    # a trace without the longest bucket's program
    ({KERNEL % "": 0.02}, {"jit_fwd_prefill_8192": {
        "count": 1, "seconds": 0.2, "by_plane": {}}}),
    (None, None),                      # a plain run, a CPU: no traced fill
])
def test_reader_returns_nothing_where_there_is_nothing(make_run, ops,
                                                       modules):
    assert READER.read(solar_run(make_run, ops, modules)) is None


def test_reader_reads_no_other_configurations_run(make_run):
    other = make_run("laguna_code_context_decode")
    other.obs["solar_fill"] = {"plen": 16384, "trace": {
        "ops": {KERNEL % "": 0.02}, "modules": {"jit_fwd_prefill_16384": {
            "count": 1, "seconds": 0.4, "by_plane": {}}}}}
    assert READER.read(other) is None


def test_a_traced_rehearsal_of_the_cell_leaves_it_out():
    """The rehearsal's heads are 16 wide and it runs on the CPU: the scan
    takes the XLA form, the reader finds no kernel and the line leaves the
    metric out, as the parent's does on the chip."""
    p = run_command(ROOT, ["--workload", CELL, "--seed", "3000000101",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["failed"] == 0
    assert NAME not in line["metrics"]
    assert "engine_fill_rows_chunked_pct" in line["metrics"]
