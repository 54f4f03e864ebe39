"""Wide&Deep CTR training through the Dataset trainer path — the classic
high-throughput recommendation workflow (ref: train_from_dataset +
InMemoryDataset + MultiSlot files + data_generator).

Pipeline demonstrated end to end:
1. a MultiSlotDataGenerator writes MultiSlot text shards (in production
   this runs as `dataset.set_pipe_command("python my_gen.py")` over raw
   logs; here we pre-materialize the shards)
2. InMemoryDataset loads + locally shuffles them with parser threads
3. exe.train_from_dataset consumes every batch through the jitted step,
   batches staged via the native C++ ring

Run: python examples/train_ctr_from_dataset.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid.incubate.data_generator import (  # noqa: E402
    MultiSlotDataGenerator,
)

N_SPARSE, VOCAB, N_DENSE = 8, 1000, 4


class CTRGenerator(MultiSlotDataGenerator):
    """Synthesizes click logs; in real use generate_sample parses a raw
    log line instead of drawing randoms."""

    def __init__(self, seed, n):
        super().__init__()
        self.rng = np.random.default_rng(seed)
        self.n = n

    def generate_sample(self, line):
        def it():
            for _ in range(self.n):
                sparse = self.rng.integers(
                    0, VOCAB, size=N_SPARSE).tolist()
                dense = [round(float(x), 4)
                         for x in self.rng.random(N_DENSE)]
                label = [int(sparse[0] % 2)]
                yield [("sparse", sparse), ("dense", dense),
                       ("click", label)]
        return it


def write_shards(tmpdir, n_shards=4, rows_per_shard=512):
    files = []
    for k in range(n_shards):
        path = os.path.join(tmpdir, "ctr_part_%d.txt" % k)
        with open(path, "w") as f:
            CTRGenerator(seed=k, n=rows_per_shard).run_from_memory(out=f)
        files.append(path)
    return files


def build_model():
    sparse = fluid.data("sparse", shape=[None, N_SPARSE], dtype="int64")
    dense = fluid.data("dense", shape=[None, N_DENSE], dtype="float32")
    label = fluid.data("click", shape=[None, 1], dtype="int64")
    emb = fluid.layers.embedding(sparse, size=[VOCAB, 16])
    deep = fluid.layers.concat(
        [fluid.layers.reshape(emb, [0, N_SPARSE * 16]), dense], axis=1)
    for width in (64, 32):
        deep = fluid.layers.fc(deep, width, act="relu")
    wide = fluid.layers.fc(dense, 1, bias_attr=False)
    logit = fluid.layers.elementwise_add(
        fluid.layers.fc(deep, 1), wide)
    prob = fluid.layers.sigmoid(logit)
    loss = fluid.layers.mean(fluid.layers.log_loss(
        fluid.layers.clip(prob, 1e-7, 1 - 1e-7),
        fluid.layers.cast(label, "float32")))
    return [sparse, dense, label], loss


def main():
    tmpdir = tempfile.mkdtemp(prefix="ctr_dataset_")
    files = write_shards(tmpdir)
    use_vars, loss = build_model()
    fluid.optimizer.Adam(1e-2).minimize(loss)

    dataset = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    dataset.set_batch_size(64)
    dataset.set_thread(2)
    dataset.set_filelist(files)
    dataset.set_use_var(use_vars)
    dataset.load_into_memory()
    dataset.local_shuffle()
    print("loaded %d samples from %d shards"
          % (dataset.get_memory_data_size(), len(files)))

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for epoch in range(3):
        dataset.local_shuffle()
        exe.train_from_dataset(
            program=fluid.default_main_program(), dataset=dataset,
            fetch_list=[loss], fetch_info=["loss"], print_period=8)
        print("epoch %d done" % epoch)
    dataset.release_memory()


if __name__ == "__main__":
    main()
