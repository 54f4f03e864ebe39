"""Train a CTR embedding the fluid way, lift it into an ep-sharded
table, and serve exact top-k search over HTTP — the parameter-server
migration path end to end.

Run with 8 virtual devices to see real sharding on a CPU host:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python examples/retrieval_serving.py
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import urllib.request

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import retrieval, serving
from paddle_tpu.models import wide_deep as wd


def main():
    # 1) train the wide&deep CTR model a few steps (fluid front end —
    #    the shared `ctr_emb` table is an ordinary parameter here)
    fluid.default_startup_program().random_seed = 7
    vs = wd.build_wide_deep(num_sparse_fields=6, sparse_vocab=2000,
                            emb_dim=16, num_dense=8, hidden=[32])
    fluid.optimizer.Adam(1e-2).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    dense, sparse, label = wd.synthetic_ctr_batch(
        256, num_sparse_fields=6, sparse_vocab=2000, num_dense=8)
    for i in range(5):
        loss = exe.run(
            feed={"dense": dense, "sparse": sparse, "ctr_label": label},
            fetch_list=[vs["loss"]])[0]
    print("trained 5 steps, loss", float(np.asarray(loss)))

    # 2) lift the trained rows out of the scope into a sharded table —
    #    where the reference sent them to parameter servers
    trained = np.asarray(
        fluid.global_scope().find_var("ctr_emb").get_tensor())
    tbl = retrieval.ShardedEmbeddingTable.from_array(
        trained, name="ctr_emb")
    info = tbl.index_info()
    print("sharded table: %d rows x %d dims over %d shard(s), "
          "%.2f MB resident (%.2f MB/shard)"
          % (info["rows"], info["dim"], info["shards"],
             info["resident_bytes"] / 1e6,
             info["resident_bytes_per_shard"] / 1e6))
    ids = np.array([3, 14, 159])
    assert np.array_equal(tbl.lookup(ids), trained[ids])  # bit for bit

    # 3) serve it: price the ladder, warm it, publish, query over HTTP
    eng = retrieval.RetrievalEngine(tbl, k=5, query_buckets=(1, 4, 16))
    eng.check_hbm_budget()  # raises predicted-oom: BEFORE any compile
    eng.warmup()
    reg = serving.ModelRegistry()
    reg.publish("items", eng)
    srv = serving.ServingServer(reg).start()
    try:
        q = trained[[42, 7]]  # items as their own queries
        req = urllib.request.Request(
            srv.url + "/v1/models/items:search",
            data=json.dumps({"query": q.tolist(), "k": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            doc = json.loads(resp.read())
        print("top-5 for item 42:", doc["ids"][0])
        # exact brute force agrees — recall@5 is 1.0 by construction
        ref = np.argsort(-(q @ trained.T), axis=1)[:, :5]
        assert np.array_equal(np.asarray(doc["ids"]), ref)
        with urllib.request.urlopen(srv.url + "/healthz",
                                    timeout=10) as r:
            hz = json.loads(r.read())
        print("healthz index block:",
              json.dumps(hz["models"]["items"]["index"]))
    finally:
        srv.stop(close_registry=True)


if __name__ == "__main__":
    main()
