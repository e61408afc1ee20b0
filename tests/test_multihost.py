"""Multi-host wiring test (reference: Network::Init rank discovery,
application.cpp:167-178, linkers_socket.cpp:20-47).

Launches a real 2-process jax.distributed CPU cluster — each process is a
separate interpreter wired through the reference's `machines` /
`local_listen_port` / `num_machines` params — trains `tree_learner=data`,
and asserts the resulting model is identical to a single-process run over a
2-device mesh (the collectives are the same psum_scatter/all_gather; only
the transport differs).
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

HERE = os.path.dirname(__file__)


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _run_cluster(tmp_path, mode: str) -> str:
    port0, port1 = _free_ports(2)
    out_model = str(tmp_path / f"mh_model_{mode}.txt")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        # repo root only: keeps lightgbm_tpu importable; children are
        # pure-CPU workers
        "PYTHONPATH": os.path.dirname(HERE),
    })
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(HERE, "multihost_child.py"),
         str(rank), str(port0), str(port1), out_model, mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for rank in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
    assert os.path.exists(out_model)
    with open(out_model) as fh:
        return fh.read()


@pytest.mark.slow
def test_two_process_data_parallel_matches_single_process(tmp_path):
    multihost_text = _run_cluster(tmp_path, "full")

    # single-process oracle: same data/params over a 2-device local mesh
    rng = np.random.RandomState(7)
    X = rng.rand(4000, 10)
    y = X[:, 0] * 3 + X[:, 1] ** 2 + 0.1 * rng.randn(4000)
    params = {"objective": "regression", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 20, "max_bin": 63, "tree_learner": "data",
              "device": "cpu", "num_machines": 2}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    assert multihost_text.strip() == bst.model_to_string().strip()


def _assert_models_match(text_a: str, text_b: str, rtol=1e-4):
    """Structural equality (splits, thresholds, counts line-exact) + numeric
    closeness for the float-valued lines: pre-partitioning moves rows between
    devices, which regroups f32 partial sums — last-ULP value drift with
    identical tree structure is the expected (and correct) outcome."""
    la, lb = text_a.strip().splitlines(), text_b.strip().splitlines()
    assert len(la) == len(lb), (len(la), len(lb))
    float_keys = ("split_gain=", "leaf_value=", "internal_value=",
                  "threshold=")
    for a, b in zip(la, lb):
        if any(a.startswith(k) for k in float_keys):
            ka, va = a.split("=", 1)
            kb, vb = b.split("=", 1)
            assert ka == kb, (a, b)
            fa = np.array([float(x) for x in va.split()])
            fb = np.array([float(x) for x in vb.split()])
            np.testing.assert_allclose(fa, fb, rtol=rtol, atol=1e-6,
                                       err_msg=ka)
        else:
            assert a == b, (a, b)


@pytest.mark.slow
def test_two_process_pre_partitioned_matches_single_process(tmp_path):
    """is_pre_partition=true: each process loads ONLY its own disjoint row
    shard (reference dataset_loader.cpp:159-221); the resulting model must
    match a single-process run over the concatenated data (structure exact,
    values to f32 accumulation tolerance)."""
    multihost_text = _run_cluster(tmp_path, "prepart")

    rng = np.random.RandomState(7)
    X = rng.randint(0, 32, size=(4000, 10)) / 31.0
    y = X[:, 0] * 3 + X[:, 1] ** 2 + 0.1 * rng.randn(4000)
    params = {"objective": "regression", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 20, "max_bin": 63, "tree_learner": "data",
              "device": "cpu", "num_machines": 2}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    _assert_models_match(multihost_text, bst.model_to_string())


from rank_data import rank_data as _rank_data


@pytest.mark.slow
def test_two_process_pre_partitioned_lambdarank(tmp_path):
    """Pre-partitioned RANKING data: whole queries per shard + init_score
    (reference Metadata::CheckOrPartition, metadata.cpp:97-127). The model
    must match a single-process run over the concatenated queries."""
    multihost_text = _run_cluster(tmp_path, "prepart_rank")

    X, y, sizes, init = _rank_data()
    params = {"objective": "lambdarank", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 20, "max_bin": 63, "tree_learner": "data",
              "device": "cpu", "num_machines": 2}
    bst = lgb.train(params,
                    lgb.Dataset(X, label=y, group=sizes, init_score=init),
                    num_boost_round=5)
    _assert_models_match(multihost_text, bst.model_to_string())


@pytest.mark.slow
def test_two_process_pre_partitioned_efb(tmp_path):
    """EFB under is_pre_partition: every rank plans bundles from the
    KV-allgathered common row sample (the reference plans
    from the same distributed sample it bins from,
    dataset_loader.cpp:820-899), so the 2-process pre-partitioned model
    matches a single-process run over the concatenated data."""
    multihost_text = _run_cluster(tmp_path, "prepart_efb")

    rng = np.random.RandomState(7)
    X = np.zeros((4000, 24))
    owner = rng.randint(0, 24, size=4000)
    X[np.arange(4000), owner] = rng.randint(1, 8, size=4000) / 7.0
    y = X[:, 0] - X[:, 1] + 0.5 * X[:, 2] + 0.05 * rng.randn(4000)
    params = {"objective": "regression", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "max_bin": 63, "tree_learner": "data",
              "device": "cpu", "num_machines": 2}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5,
                    keep_training_booster=True)
    assert bst._gbdt.bundle is not None, "EFB must engage (single-process)"
    _assert_models_match(multihost_text, bst.model_to_string())


@pytest.mark.slow
def test_two_process_voting_trains(tmp_path):
    """PV-Tree voting over a real 2-process cluster: the top-k vote psum and
    selective histogram reduction ride the coordination-service transport;
    quality is checked against the data the cluster trained on."""
    text = _run_cluster(tmp_path, "voting")
    # model parses and predicts close to the data it was trained on
    rng = np.random.RandomState(7)
    X = rng.rand(4000, 10)
    y = X[:, 0] * 3 + X[:, 1] ** 2 + 0.1 * rng.randn(4000)
    bst = lgb.Booster(model_str=text)
    mse = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse < float(np.var(y)) * 0.5, mse
