"""Child process for the multi-host tests: trains data-parallel over a
2-process jax.distributed CPU cluster wired through the reference's network
params (machines + local_listen_port + num_machines) and writes the model
from rank 0.

Modes (reference dataset_loader.cpp:159-221, tree_learner.cpp:9-33):
- full:    every process loads the full data (the non-pre-partitioned path;
           jax shards rows across the mesh), tree_learner=data
- prepart: is_pre_partition=true — each process loads ONLY its own row
           shard; global rows are assembled as per-process blocks
- voting:  full data per process, tree_learner=voting (PV-Tree top-k)

Usage: python multihost_child.py <rank> <port0> <port1> <out_model> [mode]
"""
import sys

rank, port0, port1, out_model = (int(sys.argv[1]), int(sys.argv[2]),
                                 int(sys.argv[3]), sys.argv[4])
mode = sys.argv[5] if len(sys.argv) > 5 else "full"

import numpy as np
import lightgbm_tpu as lgb

from rank_data import rank_data as _rank_data   # sys.path[0] == tests/

rng = np.random.RandomState(7)
if mode in ("prepart", "prepart_rank"):
    # discrete feature values: every shard sees the same distinct set, so
    # distributed bin finding (feature-sharded, local-sample) produces the
    # same mappers as a full-data single-process run — making the oracle
    # comparison exact
    X = rng.randint(0, 32, size=(4000, 10)) / 31.0
elif mode == "prepart_efb":
    # near-exclusive discrete features: EFB engages, planned from the
    # KV-allgathered common sample so every rank derives the identical
    # bundling (reference plans bundles from the distributed sample it
    # bins from, dataset_loader.cpp:820-899)
    X = np.zeros((4000, 24))
    owner = rng.randint(0, 24, size=4000)
    X[np.arange(4000), owner] = rng.randint(1, 8, size=4000) / 7.0
else:
    X = rng.rand(4000, 10)
if mode == "prepart_efb":
    y = X[:, 0] - X[:, 1] + 0.5 * X[:, 2] + 0.05 * rng.randn(4000)
else:
    y = X[:, 0] * 3 + X[:, 1] ** 2 + 0.1 * rng.randn(4000)

params = {
    "objective": "regression", "verbose": -1, "num_leaves": 15,
    "min_data_in_leaf": 20, "max_bin": 63, "tree_learner": "data",
    "device": "cpu", "num_machines": 2,
    "machines": f"127.0.0.1:{port0},127.0.0.1:{port1}",
    "local_listen_port": port0 if rank == 0 else port1,
}
if mode in ("prepart", "prepart_efb"):
    params["is_pre_partition"] = True
    if mode == "prepart_efb":
        params["min_data_in_leaf"] = 5
    lo, hi = rank * 2000, (rank + 1) * 2000
    ds = lgb.Dataset(X[lo:hi], label=y[lo:hi])
elif mode == "prepart_rank":
    # pre-partitioned lambdarank: each rank holds WHOLE queries (reference
    # metadata.cpp:97-127) plus its slice of init_score; blocks are
    # intentionally unequal
    X, y, sizes, init = _rank_data()
    params["objective"] = "lambdarank"
    params["is_pre_partition"] = True
    cum = np.cumsum(sizes)
    qcut = int(np.searchsorted(cum, 2000))
    rowcut = int(cum[qcut - 1]) if qcut else 0
    if rank == 0:
        ds = lgb.Dataset(X[:rowcut], label=y[:rowcut], group=sizes[:qcut],
                         init_score=init[:rowcut])
    else:
        ds = lgb.Dataset(X[rowcut:], label=y[rowcut:], group=sizes[qcut:],
                         init_score=init[rowcut:])
else:
    if mode == "voting":
        params["tree_learner"] = "voting"
        params["top_k"] = 5
    ds = lgb.Dataset(X, label=y)
bst = lgb.train(params, ds, num_boost_round=5,
                keep_training_booster=(mode in ("prepart", "prepart_efb")))
if mode == "prepart_efb":
    assert bst._gbdt.bundle is not None, "EFB must engage under pre-partition"
if mode == "prepart":
    # C-API LGBM_BoosterGetPredict under is_pre_partition must select the
    # real rows out of the block-padded device layout (_real_rows)
    # in global block order — compare against host-tree predictions
    # of the full matrix. _fetch allgathers across processes, so BOTH
    # ranks make the same calls.
    import ctypes

    from lightgbm_tpu import capi_impl

    h = capi_impl._register(bst)
    n_pred = capi_impl.booster_get_num_predict(h, 0)
    assert n_pred == 4000, n_pred
    buf = (ctypes.c_double * n_pred)()
    n_out = capi_impl.booster_get_predict(h, 0, ctypes.addressof(buf))
    got = np.frombuffer(buf, dtype=np.float64, count=n_out)
    want = bst.predict(X)              # global rows, block order = original
    assert np.allclose(got, want, rtol=1e-4, atol=1e-5), \
        float(np.abs(got - want).max())
    print(f"rank {rank} capi get_predict prepart OK", flush=True)

import jax
assert jax.process_count() == 2, jax.process_count()
if jax.process_index() == 0:
    bst.save_model(out_model)
print(f"rank {rank} done", flush=True)
