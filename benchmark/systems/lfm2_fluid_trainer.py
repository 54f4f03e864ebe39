"""System under test: LFM2-MoE next-token pretraining through the Fluid main
path, `build_lfm2_pretrain` + `decorate(Adam, use_bf16=True)` run by
`fluid.Executor.run` on one chip: one chip's share of an expert-parallel
step (the configuration's `deployment`), without the exchange; nothing
stands in for the absent chips. The weights are the benchmark's own, made
on the device from the seed and written over what the start-up program
initialised.

The driver `train_loop` feeds `input_ids` and `mlm_labels`; its mix has no
masked-LM corruption (`mask_rate` 0), so the ids arrive untouched and the
labels fed are the next tokens, made here from the ids once per ring batch.

Each step also fetches what the program counts on the device (per expert
layer the assignments that landed on held experts, the largest count on
one held expert, the held experts that got any; the head's labelled rows
and chunks), as device arrays that nobody reads while the window runs:
`close` sums the window's and publishes them as `run.obs["counters"]`
(and, through `lfm2.step_counters`, to the program's telemetry hub). The
program moves each router's `expert_bias` every step by the balancing rule
(`optimizer.expert_bias_update_rate`, mirrored in the reference's `follow`),
which holds the held experts' load at the deployment's through a window; a
note gives the window's first and last ten steps."""
# the model module first: a checkout whose paddle_tpu has no such model
# ends here, before any device work, with nothing on stdout
from paddle_tpu.models import lfm2

import numpy as np

from benchmark.costs_lfm2 import sizes  # the reference's `m` too
from benchmark.reference import lfm2_moe_lm
from benchmark.systems.bert_fluid_trainer import compare


class Trainer:
    def __init__(self, run):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import executor, framework, unique_name
        from paddle_tpu.fluid.contrib.mixed_precision import decorate

        self.run = run
        m, opt = sizes(run.config), run.config["optimizer"]
        self.model, self.optimizer = m, opt
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        executor._scope_stack[:] = [executor.Scope()]
        cfg = lfm2.Lfm2Config.from_hf(
            m, router_experts=m["router_experts"],
            first_expert=m["first_expert"],
            bias_update_rate=opt["expert_bias_update_rate"])
        for prog in (fluid.default_main_program(),
                     fluid.default_startup_program()):
            prog.random_seed = 1   # the same programs in every run
        vs = lfm2.build_lfm2_pretrain(cfg, run.traffic["seq_len"])
        adam = fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"])
        decorate(adam, use_bf16=True).minimize(vs["loss"])
        self.fetches = [vs["loss"], vs["moe_counts"], vs["head_rows"],
                        vs["head_chunks"]]
        run.mark("program built")
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        run.mark("start-up program")
        self.scope = fluid.global_scope()
        self.program = fluid.default_main_program()
        self.names = lfm2_moe_lm.trained(m)
        self._norms = jax.jit(lambda t: {
            n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in t.items()})
        self._distance = jax.jit(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        self._labels, self._counted = {}, []
        self.seed_weights(run.seed)
        run.mark("seeded weights")

    def seed_weights(self, seed, fresh_state=False):
        """Write the benchmark's weights for `seed` over the program's, a
        leaf at a time; with `fresh_state` the optimizer's state is
        initialised anew first (benchmark/limits.py reads many seeds from
        one compiled step)."""
        import paddle_tpu.fluid as fluid

        if fresh_state:
            # drop the old state first: two copies of 6.1 GB do not fit
            # beside the loaded step's scratch
            for name in list(self.scope.keys()):
                self.scope.pop(name)
            self.exe.run(fluid.default_startup_program())
        self.seed = seed
        names = []
        for name, value in lfm2_moe_lm.iter_weights(self.model, seed):
            if name not in self.scope:
                raise KeyError("the program has no parameter %r" % name)
            self.scope.update(name, value)
            names.append(name)
        # the routers' score corrections, balanced over the leaves the
        # scope now holds (no second copy of the weights)
        held = {n: self.scope.find_value(n) for n in names}
        for name, value in lfm2_moe_lm.balanced_expert_bias(
                held, self.model, seed).items():
            self.scope.update(name, value)

    def step(self, feed):
        """One training step as a user calls it; the loss and the counts
        stay on the device."""
        ids = feed["input_ids"]
        labels = self._labels.get(id(ids))
        if labels is None:
            labels = self._labels[id(ids)] = (
                lfm2_moe_lm.next_token_labels(ids))
        out = self.exe.run(self.program,
                           feed={"input_ids": ids, "labels": labels},
                           fetch_list=self.fetches, return_numpy=False)
        self._counted.append(out[1:])
        return out[0]

    def first_gradient_norms(self):
        """Per-leaf norm of the gradient the optimizer got in step 1, from
        Adam's first moment after that step: m1 = (1 - beta1) * g."""
        k = 1.0 / (1.0 - self.optimizer["beta1"])
        moments = {n: self.scope.find_value(n + "_moment1_0")
                   for n in self.names}
        return {n: k * float(v) for n, v in self._norms(moments).items()}

    def change_norms(self):
        """Per-leaf norm of what the steps so far changed: each trained
        leaf against the seeded one, made again from the seed a leaf at a
        time (a second copy of the weights held through the first steps
        would be 2 GB that the step's scratch needs)."""
        return {name: float(self._distance(self.scope.find_value(name), leaf))
                for name, leaf in lfm2_moe_lm.iter_weights(self.model,
                                                           self.seed)
                if name in self.names}

    def window_counters(self):
        """What the program counted over the steps of the window (the last
        `run.obs["steps"]` calls of `step`); None before a window."""
        steps = self.run.obs.get("steps")
        if not steps or len(self._counted) < steps:
            return None
        moe, rows, chunks = (np.stack([np.asarray(c[i]) for c in
                                       self._counted[-steps:]])
                             for i in range(3))
        by_step, layers = moe[:, :, 0].sum(1), moe.shape[1]
        self.run.note("assignments on held experts per step and expert "
                      "layer: first ten steps %.0f, last ten %.0f"
                      % (by_step[:10].mean() / layers,
                         by_step[-10:].mean() / layers))
        return lfm2.step_counters(moe, rows, chunks, steps=steps)

    def close(self):
        from paddle_tpu.fluid import executor

        counters = self.window_counters()
        if counters is not None:
            self.run.obs["counters"] = counters
        self._counted = []
        self.exe = self.program = None
        executor._scope_stack[:] = [executor.Scope()]
        self.scope = None


def build(run):
    return Trainer(run)


def check(run, sut, precision="float32"):
    """The plain reference follows the same first steps from the same
    seeded weights on the same batches, after the program's state is
    freed; `precision` other than float32 is the control, which stands in
    the program's place."""
    got = run.obs["first_steps"]
    batches = [(ids, lfm2_moe_lm.next_token_labels(ids))
               for ids, _ in run.obs["first_batches"]]
    want = lfm2_moe_lm.follow(
        sut.model, run.seed, batches, sut.optimizer, precision=precision,
        block_rows=run.config["check"]["block_rows"])
    compare(run, got, want, run.config["check"]["limits"])
    return want

