"""System under test: BERT MLM pretraining through the Fluid main path,
`build_bert_pretrain` + `decorate(Adam, use_bf16=True)` run by
`fluid.Executor.run`, on one chip or data-parallel over several through
`CompiledProgram.with_data_parallel`. The weights are the benchmark's own,
made on the device from the seed and written over what the start-up
program initialised."""
import statistics

import numpy as np

from benchmark.reference import bert_mlm


class Trainer:
    def __init__(self, run):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import executor, framework, unique_name
        from paddle_tpu.fluid.contrib.mixed_precision import decorate
        from paddle_tpu.models import bert

        self._jax = jax
        m, opt = run.config["model"], run.config["optimizer"]
        self.model, self.optimizer = m, opt
        if m["hidden_dropout_prob"] != m["attention_probs_dropout_prob"]:
            raise ValueError("models/bert.py has one dropout rate for the "
                             "hidden states and the attention probabilities")
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        executor._scope_stack[:] = [executor.Scope()]
        cfg = bert.BertConfig(
            vocab_size=m["vocab_size"], hidden=m["hidden_size"],
            num_layers=m["num_hidden_layers"],
            heads=m["num_attention_heads"], ffn=m["intermediate_size"],
            max_seq=m["max_position_embeddings"],
            type_vocab=m["type_vocab_size"],
            dropout=m["hidden_dropout_prob"], use_fused_attention=False)
        for prog in (fluid.default_main_program(),
                     fluid.default_startup_program()):
            prog.random_seed = 1   # the same programs in every run
        vs = bert.build_bert_pretrain(cfg, run.traffic["seq_len"])
        decorate(fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]),
            use_bf16=True).minimize(vs["loss"])
        self.loss = vs["loss"]
        run.mark("program built")
        self.exe = fluid.Executor()
        self.exe.run(fluid.default_startup_program())
        run.mark("start-up program")
        self.scope = fluid.global_scope()
        self.program = fluid.default_main_program()
        if run.traffic.get("data_parallel"):
            self.program = fluid.CompiledProgram(
                self.program).with_data_parallel(loss_name=self.loss.name)
        self.names = sorted(bert_mlm.weight_shapes(m))
        self._norms = jax.jit(lambda t: {
            n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in t.items()})
        self._delta = jax.jit(lambda a, b: {
            n: jnp.sqrt(jnp.sum(jnp.square(a[n] - b[n]))) for n in a})
        self.seed_weights(run.seed)
        run.mark("seeded weights x2")

    def seed_weights(self, seed, fresh_state=False):
        """Write the benchmark's weights for `seed` over the program's;
        with `fresh_state` the optimizer's state is initialised anew first
        (benchmark/limits.py reads many seeds from one compiled step)."""
        import paddle_tpu.fluid as fluid

        m = self.model
        if fresh_state:
            self.exe.run(fluid.default_startup_program())
        for name, value in bert_mlm.make_weights(m, seed).items():
            if name not in self.scope:
                raise KeyError("the program has no parameter %r" % name)
            self.scope.update(name, value)
        # a second copy of the seeded weights, to measure the change from
        self._w0 = bert_mlm.make_weights(m, seed)

    def step(self, feed):
        """One training step as a user calls it; the loss stays on the
        device."""
        return self.exe.run(self.program, feed=feed, fetch_list=[self.loss],
                            return_numpy=False)[0]

    def _gather(self, suffix=""):
        """The named leaves (or their optimizer state), brought to one
        device: under data parallelism they are replicated over all."""
        dev = self._jax.devices()[0]
        out = {}
        for n in self.names:
            v = self.scope.find_value(n + suffix)
            out[n] = self._jax.device_put(
                v.addressable_shards[0].data if len(v.devices()) > 1 else v,
                dev)
        return out

    def first_gradient_norms(self):
        """Per-leaf norm of the gradient the optimizer got in step 1, from
        Adam's first moment after that step: m1 = (1 - beta1) * g."""
        k = 1.0 / (1.0 - self.optimizer["beta1"])
        return {n: k * float(v) for n, v in
                self._norms(self._gather("_moment1_0")).items()}

    def change_norms(self):
        out = {n: float(v) for n, v in
               self._delta(self._gather(), self._w0).items()}
        self._w0 = None
        return out

    def close(self):
        from paddle_tpu.fluid import executor

        self.exe = self.program = self._w0 = None
        executor._scope_stack[:] = [executor.Scope()]
        self.scope = None


def build(run):
    return Trainer(run)


def worst_leaf_gap(got, want):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for n, ref in want.items():
        gap = abs(got[n] - ref) / max(ref, floor)
        if not np.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def compare(run, got, want, limits):
    """Fill run.compared from the program's readings and the reference's."""
    loss_gap = max(abs(g - w) / abs(w)
                   for g, w in zip(got["loss"], want["loss"]))
    grad_gap, grad_leaf = worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    chg_gap, chg_leaf = worst_leaf_gap(got["change_norm"],
                                       want["change_norm"])
    run.note("losses program %s reference %s; worst gradient leaf %s; "
             "worst change leaf %s" % (got["loss"], want["loss"], grad_leaf,
                                       chg_leaf))
    for name, value in (("loss_rel_gap", loss_gap),
                        ("grad_norm_rel_gap", grad_gap),
                        ("change_norm_rel_gap", chg_gap)):
        run.compared[name] = {"value": float(value), "limit": limits[name]}


def check(run, sut, precision="float32"):
    """The plain reference follows the same first steps from the same
    seeded weights on the same batches, after the program's state is
    freed; `precision` other than float32 is the control, which stands in
    the program's place and so has dropout masks of its own."""
    import jax

    got = run.obs["first_steps"]
    w0 = bert_mlm.make_weights(sut.model, run.seed)
    want = bert_mlm.follow(
        w0, run.obs["first_batches"], sut.model, sut.optimizer,
        precision=precision, block_rows=run.config["check"]["block_rows"],
        mask_seed=run.seed + (precision != "float32"),
        devices=jax.devices()[:run.chips])
    compare(run, got, want, run.config["check"]["limits"])
    return want
