"""Auto-parallelism planner: enumeration, pricing, search, CLI.

The slow measured-vs-predicted zoo validation lives in
test_planner_zoo.py; everything here is tier-1 (fast, deterministic).
"""
import json
import os
import subprocess
import sys

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.analysis import costs as costs_mod
from paddle_tpu.analysis.cli import _bench_bert_program, _parse_mesh
from paddle_tpu.parallel.mesh import factorizations
from paddle_tpu.planner import (ParallelPlan, enumerate_plans, plan_search,
                                price_composition, price_plan,
                                tp_compatible)

pytestmark = pytest.mark.planner

V5E = costs_mod.device_profile("v5e")


@pytest.fixture(scope="module")
def bert_search():
    """One shared 8-device search over the bench BERT pretrain program
    (the module builds its own Program; nothing leaks into the default
    program the autouse fixture manages)."""
    prog, feed_names, fetch_names = _bench_bert_program(batch=8)
    return plan_search(prog, 8, profile=V5E, feed_names=feed_names,
                       fetch_names=fetch_names, default_dim=8)


# -- mesh factorizations (satellite: parallel/mesh helper) ---------------

class TestFactorizations:
    def test_eight_over_three_axes(self):
        got = factorizations(8, axes=("dp", "tp", "pp"))
        # ordered factorizations of 2^3 over 3 slots: C(5,2) = 10
        assert len(got) == 10
        assert {"dp": 8} in got
        assert {"dp": 4, "tp": 2} in got
        assert {"dp": 2, "tp": 2, "pp": 2} in got
        assert {"tp": 8} in got
        for mesh in got:
            n = 1
            for s in mesh.values():
                n *= s
            assert n == 8

    def test_size_one_axes_dropped(self):
        for mesh in factorizations(12, axes=("dp", "tp")):
            assert all(s > 1 for s in mesh.values()) or mesh == {"dp": 1}
        assert factorizations(1) == [{"dp": 1}]

    def test_deterministic_order(self):
        assert (factorizations(24, axes=("dp", "tp", "pp"))
                == factorizations(24, axes=("dp", "tp", "pp")))

    def test_rejects_bad_count(self):
        with pytest.raises(ValueError):
            factorizations(0)


# -- candidate enumeration -----------------------------------------------

class TestEnumerate:
    def test_tp_compatible(self):
        assert tp_compatible(1, [(65, 3)])
        assert tp_compatible(4, [(64, 64), (128,)])  # 1-D params ignored
        assert not tp_compatible(4, [(64, 64), (65, 3)])
        assert tp_compatible(4, ())

    def test_plans_cover_device_count(self):
        plans = enumerate_plans(8, param_shapes=[(64, 64)])
        assert plans
        names = [p.name for p in plans]
        assert len(names) == len(set(names)), "duplicate plan names"
        for p in plans:
            assert p.n_devices == 8

    def test_comms_plans_are_pure_dp(self):
        for p in enumerate_plans(8, param_shapes=[(64, 64)]):
            if p.grad_sync_mode == "comms":
                assert set(p.mesh) == {"dp"}
            if p.sharding_degree > 1:
                assert p.dp > 1 and p.pp == 1

    def test_pp_plans_take_microbatches(self):
        plans = enumerate_plans(8, param_shapes=[(64, 64)],
                                microbatches=8)
        pp_plans = [p for p in plans if p.pp > 1]
        assert pp_plans
        assert all(p.microbatches == 8 for p in pp_plans)
        assert all(p.microbatches == 1 for p in plans if p.pp == 1)

    def test_bounds_honored(self):
        assert all(p.tp == 1 for p in
                   enumerate_plans(8, param_shapes=[(64, 64)], max_tp=1))
        assert all(p.pp == 1 for p in
                   enumerate_plans(8, param_shapes=[(64, 64)],
                                   n_layers=1))

    def test_tp_incompatible_meshes_pruned(self):
        # no parameter dim divides by 8 -> no tp=8 plan
        plans = enumerate_plans(8, param_shapes=[(6, 10)])
        assert all(p.tp in (1, 2) for p in plans)


# -- the plan record -----------------------------------------------------

class TestParallelPlan:
    def test_name_tags(self):
        assert ParallelPlan({"dp": 4, "tp": 2}, sharding_degree=4,
                            amp=True).name == "dp4_tp2+zero+amp"
        assert ParallelPlan({"dp": 8}, grad_sync_mode="comms",
                            grad_quantize=True,
                            grad_overlap=True).name == "dp8+int8+ov"
        assert ParallelPlan({"dp": 4, "pp": 2},
                            microbatches=8).name == "dp4_pp2_mb8"

    def test_roundtrip(self):
        p = ParallelPlan({"dp": 2, "tp": 2, "pp": 2}, microbatches=4,
                         grad_sync_mode="comms", grad_quantize=True,
                         sharding_degree=2, amp=True)
        assert ParallelPlan.from_dict(p.to_dict()) == p

    def test_size_one_axes_dropped(self):
        p = ParallelPlan({"dp": 8, "tp": 1, "pp": 1})
        assert p.mesh == {"dp": 8}
        assert ParallelPlan({}).mesh == {"dp": 1}

    def test_model_shards(self):
        assert ParallelPlan({"dp": 4, "tp": 2, "pp": 2}).model_shards == 4
        assert ParallelPlan({"dp": 8, "sp": 2}).model_shards == 1

    def test_fleet_runnable(self):
        assert ParallelPlan({"dp": 4, "tp": 2}).fleet_runnable()
        assert not ParallelPlan({"dp": 4, "pp": 2}).fleet_runnable()
        assert not ParallelPlan({"dp": 2, "ep": 4}).fleet_runnable()


# -- cost-model extensions (satellite: device-kind matching, DCN) --------

class TestCostModelExtensions:
    def test_pipeline_bubble_fraction(self):
        assert costs_mod.pipeline_bubble_fraction(1, 8) == 0.0
        assert costs_mod.pipeline_bubble_fraction(4, 8) == pytest.approx(
            3.0 / 8.0)
        # zero/None microbatches clamp to 1 (fully serial schedule)
        assert costs_mod.pipeline_bubble_fraction(2, 0) == 1.0
        assert costs_mod.pipeline_bubble_fraction(2, None) == 1.0

    def test_allreduce_bandwidth_wire_selection(self):
        bw, wire = costs_mod.allreduce_bandwidth(V5E, 8)
        assert (bw, wire) == (V5E.ici_bw, "ici")
        bw, wire = costs_mod.allreduce_bandwidth(
            V5E, int(V5E.slice_chips) + 1)
        assert (bw, wire) == (V5E.dcn_bw, "dcn")
        assert costs_mod.allreduce_bandwidth(None, 8) == (None, "ici")

    def test_dcn_falls_back_to_ici_when_unknown(self):
        p = V5E.copy()
        p.dcn_bw = None
        bw, wire = costs_mod.allreduce_bandwidth(p, 100000)
        assert (bw, wire) == (p.ici_bw, "ici")

    def test_v5e_vs_v5p_disambiguation(self):
        assert costs_mod.device_profile("TPU v5e").name == "v5e"
        assert costs_mod.device_profile("tpu-v5p").name == "v5p"
        assert costs_mod.device_profile("TPU v5p chip").peak_flops \
            == 459e12
        # the string a v5e chip reports maps to the v5e row
        assert costs_mod.device_profile("tpu v5 lite").name == "v5e"

    def test_device_table_order_independence(self, monkeypatch):
        kinds = ["tpu-v5e", "tpu-v5p", "tpu-v4", "tpu v6e", "v3", "v2"]
        want = [costs_mod.device_profile(k).to_dict() for k in kinds]
        monkeypatch.setattr(costs_mod, "DEVICE_TABLE",
                            list(reversed(costs_mod.DEVICE_TABLE)))
        got = [costs_mod.device_profile(k).to_dict() for k in kinds]
        assert got == want

    def test_dcn_env_overrides(self, monkeypatch):
        monkeypatch.setenv(costs_mod.DCN_BW_ENV, "5e9")
        monkeypatch.setenv(costs_mod.SLICE_CHIPS_ENV, "4")
        p = costs_mod.device_profile("v5e")
        assert p.dcn_bw == 5e9
        assert p.slice_chips == 4
        bw, wire = costs_mod.allreduce_bandwidth(p, 8)
        assert (bw, wire) == (5e9, "dcn")


# -- pricing -------------------------------------------------------------

class TestPricing:
    def test_int8_comm_beats_fp32(self, bert_search):
        base = bert_search.base
        fp32 = price_plan(base, ParallelPlan(
            {"dp": 8}, grad_sync_mode="comms", grad_quantize=False), V5E)
        int8 = price_plan(base, ParallelPlan(
            {"dp": 8}, grad_sync_mode="comms", grad_quantize=True), V5E)
        assert int8.dp_comm_seconds < fp32.dp_comm_seconds
        assert 0.0 <= int8.overlap_ratio <= 1.0
        assert int8.exposed_comm_seconds == pytest.approx(
            int8.dp_comm_seconds * (1.0 - int8.overlap_ratio))

    def test_amp_speeds_compute_and_trims_peak(self, bert_search):
        base = bert_search.base
        off = price_plan(base, ParallelPlan({"dp": 8}), V5E)
        on = price_plan(base, ParallelPlan({"dp": 8}, amp=True), V5E)
        assert on.compute_seconds < off.compute_seconds
        assert on.peak_hbm_bytes < off.peak_hbm_bytes

    def test_pipeline_bubble_inflates_compute(self, bert_search):
        base = bert_search.base
        flat = price_plan(base, ParallelPlan({"dp": 8}), V5E)
        piped = price_plan(base, ParallelPlan({"dp": 4, "pp": 2},
                                              microbatches=8), V5E)
        assert piped.bubble_fraction == pytest.approx(1.0 / 8.0)
        assert piped.compute_seconds > flat.compute_seconds
        assert piped.pp_comm_seconds > 0.0

    def test_dcn_wire_past_slice_cap(self, bert_search):
        base = bert_search.base
        small_slice = V5E.copy()
        small_slice.slice_chips = 4
        on_dcn = price_plan(base, ParallelPlan({"dp": 8}), small_slice)
        on_ici = price_plan(base, ParallelPlan({"dp": 8}), V5E)
        assert on_dcn.comm_wire == "dcn"
        assert on_ici.comm_wire == "ici"
        assert on_dcn.dp_comm_seconds > on_ici.dp_comm_seconds

    def test_zero_trims_peak(self, bert_search):
        base = bert_search.base
        plain = price_plan(base, ParallelPlan({"dp": 8}), V5E)
        zero = price_plan(base, ParallelPlan({"dp": 8},
                                             sharding_degree=8), V5E)
        assert zero.peak_hbm_bytes < plain.peak_hbm_bytes

    def test_oom_rejection_is_op_attributed(self, bert_search):
        base = bert_search.base
        priced = price_plan(base, ParallelPlan({"dp": 8}), V5E,
                            hbm_budget=1000)
        rej = priced.rejected
        assert rej is not None
        assert rej["reason"] == "predicted-oom"
        assert rej["peak_bytes"] > rej["hbm_bytes"] == 1000
        assert isinstance(rej["peak_op_index"], int)
        assert rej["peak_op_type"]
        assert rej["top_residents"] and all(
            r["name"] and r["bytes"] > 0 for r in rej["top_residents"])


# -- the search ----------------------------------------------------------

class TestPlanSearch:
    def test_ranked_ascending_and_complete(self, bert_search):
        r = bert_search
        assert r.ranked, "no plan priced"
        times = [p.predicted_step_seconds for p in r.ranked]
        assert times == sorted(times)
        assert r.best is r.ranked[0]
        assert not r.unpriced
        assert (len(r.ranked) + len(r.rejected)
                == len(enumerate_plans(
                    8, param_shapes=[s for _, s in r.base.param_shapes],
                    n_layers=max(1, r.base.n_heavy_ops // 2))))

    def test_best_runnable_is_fleet_buildable(self, bert_search):
        br = bert_search.best_runnable()
        assert br is not None and br.plan.fleet_runnable()

    def test_in_process_determinism(self, bert_search):
        prog, feed_names, fetch_names = _bench_bert_program(batch=8)
        again = plan_search(prog, 8, profile=V5E, feed_names=feed_names,
                            fetch_names=fetch_names, default_dim=8)
        assert (json.dumps(again.to_dict(), sort_keys=True)
                == json.dumps(bert_search.to_dict(), sort_keys=True))

    def test_hbm_budget_gates_before_ranking(self, bert_search):
        prog, feed_names, fetch_names = _bench_bert_program(batch=8)
        r = plan_search(prog, 8, profile=V5E, feed_names=feed_names,
                        fetch_names=fetch_names, default_dim=8,
                        base=bert_search.base, hbm_budget=1000)
        assert not r.ranked
        assert r.rejected and all(
            p.rejected["reason"] == "predicted-oom" for p in r.rejected)

    def test_render_text_mentions_oom(self, bert_search):
        prog, feed_names, fetch_names = _bench_bert_program(batch=8)
        r = plan_search(prog, 8, profile=V5E, base=bert_search.base,
                        hbm_budget=1000)
        txt = r.render_text()
        assert "OOM" in txt and "8 devices" in txt


# -- strategy ingestion (DistributedStrategy.from_plan) ------------------

class TestFromPlan:
    def _best(self, bert_search):
        return bert_search.best_runnable()

    def test_from_plan_object_and_dict(self, bert_search):
        from paddle_tpu.parallel.fleet import DistributedStrategy

        best = self._best(bert_search).plan
        for src in (best, best.to_dict()):
            s = DistributedStrategy.from_plan(src)
            assert s.tensor_parallel_degree == best.tp
            assert s.grad_sync_mode == best.grad_sync_mode
            assert s.grad_quantize == best.grad_quantize
            assert s.sharding_degree == best.sharding_degree
            assert s.amp == best.amp

    def test_from_whole_json_document(self, bert_search):
        from paddle_tpu.parallel.fleet import DistributedStrategy

        doc = {"target": "x", "devices": 8,
               "plan": bert_search.to_dict(top=3)}
        s = DistributedStrategy.from_plan(doc)
        assert s.grad_sync_mode == bert_search.best.plan.grad_sync_mode

    def test_winning_plan_runs_a_fleet_step(self, bert_search):
        """The emitted winner applies end to end: plan document ->
        from_plan -> distributed_optimizer -> one step on the 8-device
        mesh with a finite loss."""
        import numpy as np
        from paddle_tpu.parallel import fleet as fleet_mod

        doc = json.loads(json.dumps(bert_search.to_dict()))
        best = next(p for p in doc["ranked"]
                    if p["plan"]["fleet_runnable"])
        strategy = fleet_mod.DistributedStrategy.from_plan(best)
        x = fluid.data("x", [None, 64], dtype="float32")
        y = fluid.data("y", [None, 1], dtype="float32")
        h = fluid.layers.fc(x, size=64, act="relu")
        loss = fluid.layers.reduce_mean(fluid.layers.square_error_cost(
            fluid.layers.fc(h, size=1), y))
        fl = fleet_mod.Fleet().init()
        fl.distributed_optimizer(
            fluid.optimizer.Adam(learning_rate=1e-3),
            strategy).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.default_rng(0)
        out = exe.run(fl.main_program, feed={
            "x": rng.normal(size=(16, 64)).astype(np.float32),
            "y": rng.normal(size=(16, 1)).astype(np.float32)},
            fetch_list=[loss])
        assert np.isfinite(float(np.asarray(out[0])))

    def test_pp_mesh_refused(self):
        from paddle_tpu.parallel.fleet import DistributedStrategy

        with pytest.raises(NotImplementedError):
            DistributedStrategy.from_plan(
                ParallelPlan({"dp": 4, "pp": 2}))
        with pytest.raises(TypeError):
            DistributedStrategy.from_plan("dp8")


# -- the lint (satellite: suboptimal-parallel-plan) ----------------------

class TestSuboptimalPlanLint:
    def test_bad_composition_flagged(self, bert_search):
        from paddle_tpu.analysis.tpu_lint import lint_parallel_plan

        prog, _, _ = _bench_bert_program(batch=8)
        rep = lint_parallel_plan(prog, {"tp": 8}, level="full",
                                 search_result=bert_search)
        perf = [d for d in rep.diagnostics
                if d.check == "suboptimal-parallel-plan"]
        assert len(perf) == 1
        assert bert_search.best.plan.name in perf[0].message
        assert "--plan --devices 8" in perf[0].message
        assert "parallel_plan" in rep.meta
        # PERF advisories never fail a gate
        assert not rep.findings

    def test_winning_composition_clean(self, bert_search):
        from paddle_tpu.analysis.tpu_lint import lint_parallel_plan
        from paddle_tpu.parallel.fleet import DistributedStrategy

        best = bert_search.best.plan
        prog, _, _ = _bench_bert_program(batch=8)
        rep = lint_parallel_plan(
            prog, dict(best.mesh), level="full",
            strategy=DistributedStrategy.from_plan(best)
            if best.fleet_runnable() else None,
            amp=best.amp, microbatches=best.microbatches,
            search_result=bert_search)
        assert not [d for d in rep.diagnostics
                    if d.check == "suboptimal-parallel-plan"]

    def test_off_below_full_level(self, bert_search):
        from paddle_tpu.analysis.tpu_lint import lint_parallel_plan

        prog, _, _ = _bench_bert_program(batch=8)
        rep = lint_parallel_plan(prog, {"tp": 8}, level="verify",
                                 search_result=bert_search)
        assert not rep.diagnostics and "parallel_plan" not in rep.meta


# -- price_composition (the zoo/lint entry point) ------------------------

class TestPriceComposition:
    def test_strategy_attrs_read(self, bert_search):
        from paddle_tpu.parallel.fleet import DistributedStrategy

        prog, _, _ = _bench_bert_program(batch=8)
        st = DistributedStrategy()
        st.grad_sync_mode = "comms"
        st.grad_quantize = True
        priced = price_composition(prog, {"dp": 8}, strategy=st,
                                   profile=V5E, base=bert_search.base)
        assert priced.plan.grad_quantize
        assert priced.plan.name == "dp8+int8+ov"
        assert priced.predicted_step_seconds > 0.0


# -- CLI -----------------------------------------------------------------

def _run_cli(args, env_extra=None, cwd="/root/repo"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis"] + args,
        capture_output=True, text=True, env=env, cwd=cwd)


class TestCLI:
    def test_mesh_parser_accepts_pp_ep(self):
        assert _parse_mesh("dp=2,pp=2,ep=2") == {"dp": 2, "pp": 2,
                                                 "ep": 2}
        assert _parse_mesh(" dp=8 , tp=2 ") == {"dp": 8, "tp": 2}
        assert _parse_mesh(None) == {}

    @pytest.mark.parametrize("spec", ["dp", "dp=", "dp=abc", "dp=0",
                                      "dp=2,dp=4", "=4"])
    def test_mesh_parser_rejects_malformed(self, spec):
        with pytest.raises(ValueError) as ei:
            _parse_mesh(spec)
        assert "bad --mesh" in str(ei.value)

    def test_malformed_mesh_exits_2(self):
        res = _run_cli(["--plan", "--devices", "8", "--mesh", "dp=abc"])
        assert res.returncode == 2
        assert "bad --mesh" in res.stderr

    def test_plan_without_devices_exits_2(self):
        res = _run_cli(["--plan"])
        assert res.returncode == 2
        assert "--devices" in res.stderr

    def test_target_required_without_plan(self):
        res = _run_cli([])
        assert res.returncode == 2
        assert "TARGET" in res.stderr

    def test_plan_json_deterministic_across_processes(self, tmp_path):
        """Satellite: byte-identical --json-out from two fresh
        processes (no timestamps, uids, or hash-order leaks)."""
        outs = []
        for i in (1, 2):
            path = str(tmp_path / ("plan%d.json" % i))
            res = _run_cli(["--plan", "--devices", "8", "--device",
                            "v5e", "--top", "4", "--json-out", path])
            assert res.returncode == 0, res.stderr
            with open(path, "rb") as f:
                outs.append(f.read())
        assert outs[0] == outs[1]
        doc = json.loads(outs[0])
        assert doc["devices"] == 8
        plan = doc["plan"]
        assert plan["n_candidates"] >= 20
        assert plan["ranked"] and len(plan["ranked"]) <= 4
        best = plan["best"]["plan"]
        assert best["name"] and "fleet_runnable" in best
        # stdout carries the same document
        assert json.loads(res.stdout) == doc

    def test_plan_nothing_fits_exits_1(self, tmp_path):
        path = str(tmp_path / "plan.json")
        res = _run_cli(
            ["--plan", "--devices", "8", "--device", "v5e",
             "--json-out", path],
            env_extra={"PADDLE_TPU_HBM_BYTES": "1000"})
        assert res.returncode == 1, res.stderr
        doc = json.loads(open(path).read())
        assert not doc["plan"]["ranked"]
        rej = doc["plan"]["rejected"]
        assert rej
        for r in rej:
            d = r["rejected"]
            assert d["reason"] == "predicted-oom"
            assert d["peak_op_type"] and d["top_residents"]
