"""The hybrid decoder's cell (`nemotron_h_chat_decode`) at rehearsal size on
the CPU: its cost functions against the issue's arithmetic, `correct` coming
out false when the timed path is broken three ways or computed in float8,
and its readers on a run that has what they read.

The limit these tests hold the rehearsal to is 0.25 (in units of a
position's logit standard deviation): over seeds 41, 42 and 3000000043 the
sound system reads 0.01-0.14 at this size (bfloat16 activations against
float32, `initializer_range` 0.08), a state handed over at the bucket's end
1.6-5, expert weights normalised after the cut 1.2-1.5, the held range
shifted by one expert 0.56-0.96, float8's own first choice (48 requests)
0.38 and more.

`routed_gap` (the held experts' part of each expert layer, system against
reference, median over positions) is held to the rehearsal's own limit of
0.03: sound 0.009-0.013, the experts alone in float8 0.069-0.074, the routed
part dropped 1.06-1.09, the held range shifted 1.6-1.9, weights normalised
after the cut 2.9-3.1; a state taken at the bucket's end does not move it
(the parts over the prompt are causal).
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmark import costs_hybrid, harness

CELL = "nemotron_h_chat_decode"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TEST_LIMIT = 0.25


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def measure(make_run, seed):
    run = make_run(CELL, seed=seed, seconds=2)
    run.traffic["check"]["sample_requests"] = 48
    run.traffic["check"]["limits"]["logit_gap_sigma"] = TEST_LIMIT
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["failed"] == 0 and line["attempted"] > 0
    return run, line


def published():
    manifest = harness.load_manifest(ROOT)
    _, config, _ = harness.resolve_cell(manifest, CELL, root=ROOT)
    return dict(config["model"], router_experts=config["reduced_from"][
        "n_routed_experts"]), config


def test_costs_follow_the_cut_written_in_the_issue():
    m, config = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    p = costs_hybrid.block_params(m)
    assert round(p["M"] / 1e6, 1) == 109.6
    assert round(p["*"] / 1e6, 1) == 35.7
    assert round((p["E_outside"] + 128 * p["expert"]) / 1e6, 1) == 759.2
    # 21.0 MB of state-space state + 0.3 MB of windows a slot; 1 KB a row
    assert round(costs_hybrid.fixed_state_bytes(m) / 1e6, 1) == 21.3
    assert costs_hybrid.row_bytes(m) == 1024
    slots = config["serving"]["slots"]
    # all but the embedding's rows: 9.0 GB; the step about 14.6 GB, 17.8 ms
    assert round(costs_hybrid.weight_bytes(m, slots) / 1e9, 1) == 9.0
    nbytes = costs_hybrid.step_bytes(m, slots, slots * 500)
    assert 14.4e9 < nbytes < 14.8e9
    assert costs_hybrid.step_min_seconds(
        m, slots, slots * 500, peaks) == pytest.approx(nbytes / 819e9)
    # a batch-1 prefill reads the weights once: 11 ms, above its arithmetic
    least = costs_hybrid.prefill_min_seconds(m, 512, peaks)
    assert 0.0105 < least < 0.0115
    assert 512 * costs_hybrid.flops_per_token(m, 256) / 197e12 < least
    # fewer live slots touch fewer experts and move less state
    assert costs_hybrid.step_bytes(m, 16, 16 * 500) < 0.6 * nbytes


def test_the_held_state_is_what_the_configuration_file_says():
    """The declaration the engine allocates from, summed at the published
    sizes: 2.72 GB of fixed state and 0.13 GB of K/V rows for 128 slots."""
    from paddle_tpu.models import nemotron_h

    m, config = published()
    cfg = nemotron_h.NemotronHConfig.from_hf(m, router_experts=512)
    model = cfg.decode_model(config["serving"]["cache_len"])
    slots = config["serving"]["slots"]
    assert round(slots * model.slot_bytes("fixed") / 1e9, 2) == 2.72
    assert round(slots * model.slot_bytes("rows") / 1e9, 2) == 0.13
    assert model.slot_bytes("fixed") == costs_hybrid.fixed_state_bytes(m)
    assert len(model.state) == 2 + 5 * 2
    params = sum(int(np.prod(s)) for s, _ in
                 nemotron_h.param_shapes(cfg).values())
    assert round(params / 1e9, 2) == 4.65            # 9.30 GB in bfloat16


@pytest.mark.parametrize("seed", [41, 3000000043])
def test_the_sound_system_is_correct_and_its_readers_read(make_run, seed):
    run, line = measure(make_run, seed)
    assert line["correct"] is True
    assert line["compared"]["routed_gap"]["value"] <= 0.02
    c = run.obs["counters"]
    assert c["moe_assignments_total"] > c["moe_assignments_held"] > 0
    assert c["cache_copy_steps"] == 0
    per_expert = harness.load_part(
        "metrics", "moe_tokens_per_held_expert").read(run)
    # 8 of 32 experts held, top-4: under even routing a live slot lands
    # 4 * 8 / 32 = 1 assignment on the 8 held experts, so 4 live slots 0.5
    # on each; routing is not even, so the reading lies around that
    assert 0.05 < per_expert < 1.5
    skew = harness.load_part("metrics", "moe_load_max_over_mean").read(run)
    assert 1.0 <= skew <= 8.0
    gauges = run.obs["gauges"][-1]
    assert gauges["state_bytes_fixed"] > 0 and gauges["state_bytes_rows"] > 0


# Faults planted in the timed path. Each takes `patch(owner, name, value)`
# (pytest's `monkeypatch.setattr`, or plain `setattr` for a run on the chip)
# so the same faults are read here at rehearsal size and there at the timed
# size.

def state_at_the_buckets_end(patch):
    """The prefill hands over the state after the padding, not at the
    prompt's last real token."""
    from paddle_tpu.models import nemotron_h as nh

    scan, conv = nh.layers.mamba2_scan, nh.layers.causal_conv1d
    patch(nh.layers, "mamba2_scan",
          lambda *a, length=None, **k: scan(*a, **k))
    patch(nh.layers, "causal_conv1d",
          lambda *a, length=None, **k: conv(*a, **k))


def weights_normalised_after_the_cut(patch):
    """A token's weights sum to the routed scale over the HELD experts it
    chose, not over all it chose."""
    from paddle_tpu.ops import hybrid_ops

    real = hybrid_ops.held_experts_sum

    def renormalised(x, idx, wt, w1, w2, first, *args, **kw):
        here = (idx >= first) & (idx < first + w1.shape[0])
        kept = jnp.where(here, wt, 0.0)
        wt = (kept / (kept.sum(-1, keepdims=True) + 1e-20)
              * wt.sum(-1, keepdims=True))
        return real(x, idx, wt, w1, w2, first, *args, **kw)

    patch(hybrid_ops, "held_experts_sum", renormalised)


def held_range_shifted_by_one(patch):
    """The layer believes it holds experts 1..held where it holds 0..held-1."""
    from paddle_tpu.models import nemotron_h as nh

    real = nh.NemotronHConfig.from_hf.__func__
    patch(nh.NemotronHConfig, "from_hf", classmethod(
        lambda cls, m, router_experts=None, first_expert=0:
        real(cls, m, router_experts, first_expert + 1)))


def routed_part_dropped(patch):
    """The held experts add nothing (their counts still arrive)."""
    from paddle_tpu.ops import hybrid_ops

    real = hybrid_ops.held_experts_sum

    def dropped(*args, **kw):
        out, counts = real(*args, **kw)
        return jnp.zeros_like(out), counts

    patch(hybrid_ops, "held_experts_sum", dropped)


def experts_in_float8(patch):
    """Both grouped products take their operands rounded to float8 (e4m3),
    the control's precision, in the expert layer alone. `reduce_precision`
    and not a cast there and back: the TPU's compiler removed that pair
    (the step paid for a copy and the values came back unrounded)."""
    import jax

    from paddle_tpu.ops import hybrid_ops

    real = hybrid_ops.grouped_dot

    def f8(a):
        return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)

    patch(hybrid_ops, "grouped_dot",
          lambda xs, w, sizes, platform=None:
          real(f8(xs), f8(w), sizes, platform))


FAULTS = {f.__name__: f for f in (
    state_at_the_buckets_end, weights_normalised_after_the_cut,
    held_range_shifted_by_one, routed_part_dropped, experts_in_float8)}


def not_correct(make_run, monkeypatch, fault, by):
    """A run with `fault` planted reads `correct` false, by each number
    named in `by` and by no other."""
    FAULTS[fault](monkeypatch.setattr)
    _, line = measure(make_run, 41)
    assert line["correct"] is False
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert over == set(by), line["compared"]


def test_a_state_taken_at_the_buckets_end_is_not_correct(make_run,
                                                         monkeypatch):
    # the held experts' parts over the prompt are causal: they do not see it
    not_correct(make_run, monkeypatch, "state_at_the_buckets_end",
                ["logit_gap_sigma"])


def test_weights_normalised_after_the_cut_are_not_correct(make_run,
                                                          monkeypatch):
    not_correct(make_run, monkeypatch, "weights_normalised_after_the_cut",
                ["logit_gap_sigma", "routed_gap"])


def test_a_held_range_shifted_by_one_expert_is_not_correct(make_run,
                                                           monkeypatch):
    not_correct(make_run, monkeypatch, "held_range_shifted_by_one",
                ["logit_gap_sigma", "routed_gap"])


@pytest.mark.parametrize("fault", ["routed_part_dropped",
                                   "experts_in_float8"])
def test_a_fault_in_the_held_experts_alone_is_not_correct(make_run,
                                                          monkeypatch, fault):
    """What the served tokens hardly show (the held experts' part is a
    hundredth of the stream's power) `routed_gap` holds directly: dropped it
    reads about 1, in float8 0.07 against 0.009-0.013 sound."""
    FAULTS[fault](monkeypatch.setattr)
    _, line = measure(make_run, 41)
    got = line["compared"]["routed_gap"]
    assert line["correct"] is False and got["value"] > got["limit"]


@pytest.mark.parametrize("seed", [51, 52, 3000000053])
def test_the_control_in_float8_fails_the_limit(make_run, seed):
    """At each position of the same prompts and tokens, the token float8
    puts first lies further below the reference's best than the limit; the
    reference's own greedy tokens read 0."""
    from benchmark import traffic as T
    from benchmark.reference import nemotron_h_lm
    from benchmark.systems import hybrid_decode_server

    run = make_run(CELL, seed=seed)
    m = hybrid_decode_server.reference_sizes(run.config)
    cache_len = run.config["serving"]["cache_len"]
    src = T.RequestSource(run.traffic, seed, m["vocab_size"], cache_len)
    w = nemotron_h_lm.make_weights(m, seed)
    served = []
    for _ in range(48):         # greedy decoding by the reference itself
        r = src.next()
        seq = np.zeros(cache_len, np.int32)
        seq[:len(r["prompt"])] = r["prompt"]
        toks = []
        for j in range(r["max_new"]):
            at = len(r["prompt"]) + j - 1
            toks.append(int(np.asarray(nemotron_h_lm.logits_at(
                w, seq, np.asarray([at]), m))[0].argmax()))
            seq[at + 1] = toks[-1]
        served.append((list(r["prompt"]), toks))
    kw = dict(seq_len=cache_len, out_len=16)
    sound = max(g.max() for g in
                nemotron_h_lm.served_gaps(w, served, m, **kw))
    control = max(g.max() for g in nemotron_h_lm.served_gaps(
        w, served, m, control="float8", **kw))
    assert sound == 0.0 and control > TEST_LIMIT
    # and its held experts' parts lie further from the reference's than the
    # cell's own limit allows
    limit = run.traffic["check"]["limits"]["routed_gap"]
    for prompt, toks in served[:4]:
        seq = np.asarray(prompt + toks, np.int32)
        assert nemotron_h_lm.routed_gap(
            nemotron_h_lm.routed_parts(w, seq, m, "float8"),
            nemotron_h_lm.routed_parts(w, seq, m)) > limit


def test_the_kernels_reader_reads_its_own_events_and_nothing_else(make_run):
    """`gmm_roofline_pct` from a recorded table: the kernel's events by name
    (not the compiler's ragged dot), its calls counted from the traced
    executions of the step and the prefills."""
    m, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    # one expert layer, 128 live slots: 1.41 GB of expert matrices, 1.7 ms
    one = costs_hybrid.grouped_products_min_seconds(m, 128, peaks)
    assert 1.41e9 / 819e9 < one < 1.05 * 1.45e9 / 819e9
    assert costs_hybrid.grouped_products_min_seconds(
        m, 128, peaks, touched=64) < 0.55 * one
    run = make_run(CELL)
    run.config = harness.resolve_cell(run.manifest, CELL, root=ROOT)[1]
    reader = harness.load_part("metrics", "gmm_roofline_pct")
    run.obs.update(
        gauges=[{"slot_utilization": 1.0}], prompt_lens=[288],
        counters={"steps": 10, "moe_experts_touched_sum": 10 * 5 * 128},
        trace={"ops": {"%ragged-dot-none.1 = f32[2816,2688] custom-call(": 9.0},
               "modules": {"jit_fwd_decode_step": {
                   "count": 4, "seconds": 0.1, "by_plane": {}}}})
    assert reader.read(run) is None         # no such kernel in this program
    one = costs_hybrid.grouped_products_min_seconds(m, 128, peaks, 128.0)
    run.obs["trace"]["ops"]["%gmm.3 = f32[2816,1024] custom-call("] = 0.02
    run.obs["trace"]["ops"]["%gmm = f32[2816,2688] custom-call("] = 0.02
    assert reader.read(run) == pytest.approx(100 * 5 * 4 * one / 0.04)
    run.obs["trace"]["modules"]["jit_fwd_prefill_512"] = {
        "count": 2, "seconds": 0.05, "by_plane": {}}
    more = reader.read(run)
    assert more == pytest.approx(100 * 5 * (
        4 * one + 2 * costs_hybrid.grouped_products_min_seconds(
            m, 288, peaks)) / 0.04)
