"""float32 rows stay float32: ``lgb.Dataset`` keeps the user's array where
it is C-contiguous, times no float64 copy and no round-trip check, and feeds
device ingest from it; bin finding reads its row sample through float64, so
bin boundaries, codes, trees and model text are those of the same values
handed in as float64 (ISSUE 34; PERF.md, PR 34). float64 in behaves as
before.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs

ROWS = 70000          # over tpu_ingest=auto's 65,536: device ingest engages
BASE = dict(objective="binary", num_leaves=15, max_bin=63, min_data_in_leaf=20,
            learning_rate=0.2, verbose=-1, metric="none", device="cpu", seed=7)
WIDENING_GAUGES = ("setup.dataset_to_float_s", "setup.dataset_lossless_check_s")


def _table(kind: str):
    rng = np.random.RandomState(5)
    X = rng.rand(ROWS, 10).astype(np.float32)
    cat = None
    if kind == "nan":
        X[rng.rand(*X.shape) < 0.05] = np.nan
    if kind == "categorical":
        X[:, 3] = rng.randint(0, 12, ROWS)
        cat = [3]
    if kind == "efb":                   # mutually exclusive sparse columns
        owner = rng.randint(0, 6, ROWS)
        sparse = np.zeros((ROWS, 6), np.float32)
        sparse[np.arange(ROWS), owner] = rng.rand(ROWS).astype(np.float32) + 0.5
        X = np.concatenate([X[:, :4], sparse], axis=1)
    z = np.nan_to_num(X)
    y = (z[:, 0] + z[:, 1] * z[:, 2] + 0.3 * z[:, 4] > 0.9).astype(np.float32)
    return X, y, cat


CASES = {
    "serial": ("plain", {}),
    "data4": ("plain", dict(tree_learner="data", num_machines=4)),
    "efb": ("efb", dict(enable_bundle=True)),
    "categorical": ("categorical", {}),
    "nan": ("nan", {}),
    "host_ingest": ("nan", dict(tpu_ingest="host")),
}


def _train(X, y, cat, params, rounds=3):
    ds = lgb.Dataset(X, label=y, params=params, categorical_feature=cat or "auto")
    bst = lgb.Booster(params=dict(params), train_set=ds)
    for _ in range(rounds):
        bst.update()
    return ds, bst


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_in_gives_what_float64_in_gives(case):
    kind, extra = CASES[case]
    X, y, cat = _table(kind)
    params = dict(BASE, **extra)
    ds32, b32 = _train(X, y, cat, params)
    ds64, b64 = _train(X.astype(np.float64), y, cat, params)
    assert ds32.raw_data is X and ds32.raw_data.dtype == np.float32
    assert ds64.raw_data.dtype == np.float64
    c32, c64 = ds32.constructed, ds64.constructed
    assert len(c32.mappers) == len(c64.mappers)
    for m32, m64 in zip(c32.mappers, c64.mappers):
        assert m32.num_bin == m64.num_bin and m32.missing_type == m64.missing_type
        assert np.array_equal(m32.bin_upper_bound, m64.bin_upper_bound,
                              equal_nan=True)
    rows = np.arange(0, ROWS, 7)
    assert np.array_equal(c32.bin_rows(rows), c64.bin_rows(rows))
    # device ingest is fed from the float32 rows (bundling bins on the host)
    ingested = case not in ("efb", "host_ingest")
    for bst in (b32, b64):
        report = bst._gbdt._ingest_report
        assert (report["rows"] == ROWS) if ingested else (report is None)
    assert np.array_equal(np.asarray(b32._gbdt.Xb), np.asarray(b64._gbdt.Xb))
    assert b32.model_to_string() == b64.model_to_string()


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_widening_spans_run_for_float64_only(dtype):
    obs.reset_for_tests()
    try:
        X, y, _ = _table("plain")
        _train(X.astype(dtype), y, None, dict(BASE), rounds=0)
        gauges = obs.snapshot()["gauges"]
        timed = [g for g in WIDENING_GAUGES if g in gauges]
        assert timed == (list(WIDENING_GAUGES) if dtype == "float64" else [])
        assert gauges["setup.dataset_find_bins_s"] > 0
        assert gauges["setup.ingest_s"] > 0
    finally:
        obs.reset_for_tests()


@pytest.mark.parametrize("how", ["non_contiguous", "float16", "fortran"])
def test_other_layouts_and_widths_still_train(how):
    X, y, _ = _table("plain")
    if how == "non_contiguous":
        wide = np.zeros((ROWS, 20), np.float32)
        wide[:, ::2] = X
        given = wide[:, ::2]                      # a strided float32 view
    elif how == "fortran":
        given = np.asfortranarray(X)
    else:
        X = X.astype(np.float16).astype(np.float32)
        given = X.astype(np.float16)
    assert how == "float16" or not given.flags["C_CONTIGUOUS"]
    ds, bst = _train(given, y, None, dict(BASE))
    _, ref = _train(np.ascontiguousarray(X, np.float64), y, None, dict(BASE))
    assert ds.raw_data.dtype == (np.float64 if how == "float16" else np.float32)
    assert bst.model_to_string() == ref.model_to_string()
