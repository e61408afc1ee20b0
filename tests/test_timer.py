"""``tpu_time_tag`` / ``LGBM_TPU_TIMETAG``: the reference's TIMETAG summary
(compile-time accumulators in serial_tree_learner.cpp:10-37 / gbdt.cpp,
dumped at destruction), here a VIEW of the registry's always-on records:
no timer of its own (docs/Observability.md)."""
import logging

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs


@pytest.fixture(autouse=True)
def _clean():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


def test_summary_reads_the_registry():
    assert obs.time_tag_summary().startswith("TIMETAG: (no phases")
    reg = obs.get_registry()
    for seconds in (0.5, 0.25):
        reg.summary("step.host_s").observe(seconds)
    reg.summary("eval.host_s").observe(0.125)
    with obs.setup_span("dataset.construct"):
        pass
    reg.histogram("setup.dataset_construct_valid_s").observe(1.5)
    reg.histogram("setup.dataset_construct_valid_s").observe(0.5)
    lines = obs.time_tag_summary().splitlines()
    assert lines[0].startswith("TIMETAG phase summary")
    # longest first; every row a sum over the process and its count
    assert [ln.split()[0] for ln in lines[1:]] == [
        "setup.dataset_construct_valid_s", "step.host_s", "eval.host_s",
        "setup.dataset_construct_s"]
    assert "2.000s  x2" in lines[1] and "0.750s  x2" in lines[2]
    assert lines[3].endswith("x1") and lines[4].endswith("x1")


def test_train_prints_the_summary_from_the_same_boundaries(caplog):
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4)
    y = (X[:, 0] > 0.5).astype(float)
    ds = lgb.Dataset(X, label=y)
    valid = [lgb.Dataset(X[:n], label=y[:n], reference=ds) for n in (50, 80)]
    params = {"objective": "binary", "verbose": 1, "num_leaves": 4,
              "tpu_time_tag": True, "metric": "binary_logloss"}
    with caplog.at_level(logging.INFO):
        lgb.train(params, ds, num_boost_round=2, valid_sets=valid,
                  verbose_eval=False)
    printed = [r.getMessage() for r in caplog.records
               if "TIMETAG phase summary" in r.getMessage()]
    assert len(printed) == 1, [r.getMessage() for r in caplog.records]
    rows = {ln.split()[0]: ln for ln in printed[0].splitlines()[1:]}
    assert rows["step.host_s"].endswith("x2")
    assert rows["eval.host_s"].endswith("x2")
    # the training set's row is its own: two valid sets, constructed after
    # ``booster.init``, are summed beside it and do not overwrite it
    assert rows["setup.dataset_construct_s"].endswith("x1")
    assert rows["setup.dataset_construct_valid_s"].endswith("x2")
    assert {"setup.booster_init_s", "setup.finalize_fetch_s"} <= set(rows)
    # the switch prints; it times nothing: the records are there without it
    obs.reset_for_tests()
    caplog.clear()
    with caplog.at_level(logging.INFO):
        lgb.train(dict(params, tpu_time_tag=False), lgb.Dataset(X, label=y),
                  num_boost_round=2, verbose_eval=False)
    assert not any("TIMETAG" in r.getMessage() for r in caplog.records)
    assert obs.get_registry().summary("step.host_s").count == 2
