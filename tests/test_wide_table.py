"""A WIDE table through the normal path (lgb.Dataset -> lgb.Booster ->
update()) with the histogram pass's chunk sized by its width
(ops/histogram.hist_pass_shape).

At 2,048 x 384 and 64 bins a chunk's one-hot operand is far under the rule's
bytes, so the shape alone resolves to one 2,048-row chunk; with the rule's
bytes brought down to the scale of the test the SAME table resolves to eight
chunks of 256 rows. The two boosters have to grow the same trees, and the
trees have to be what a NumPy float64 valuation of their own split structure
gives (route by raw value, sum g and h per leaf, leaf values and counts).
The per-tree counters of the pass are checked against a hand count.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.ops import histogram

ROWS, COLS, TREES = 2048, 384, 5
PARAMS = dict(objective="binary", num_leaves=31, max_bin=63,
              learning_rate=0.1, min_data_in_leaf=5, verbose=-1,
              metric="none", seed=7)


def _table():
    rng = np.random.RandomState(11)
    X = rng.rand(ROWS, COLS).astype(np.float32)
    signal = (2.0 * X[:, 3] - 1.5 * X[:, 200] + 3.0 * X[:, 50] * X[:, 383]
              - 2.0 * X[:, 120] * X[:, 7])
    y = (signal + 0.3 * rng.randn(ROWS) > np.median(signal)).astype(np.float32)
    return X, y


def _train(params=PARAMS):
    X, y = _table()
    bst = lgb.Booster(params=dict(params),
                      train_set=lgb.Dataset(X, label=y, params=dict(params)))
    for _ in range(TREES):
        bst.update()
    bst._ensure_finalized()
    return bst


@pytest.fixture
def narrow_chunks(monkeypatch):
    """The rule's bytes at the scale of the test: 12 MiB holds 256 rows of
    384 columns at 64 bins x 2 bytes."""
    monkeypatch.setattr(histogram, "_ONEHOT_BYTES_A_CHUNK", 12 << 20)


@pytest.fixture
def telemetry(tmp_path):
    """Fresh process-wide singletons that record events; reset after."""
    obs.reset_for_tests()
    obs.configure(telemetry_dir=str(tmp_path))
    yield obs
    obs.reset_for_tests()


def _leaf_of_rows(tree, X):
    leaf = np.zeros(len(X), np.int64)
    node = np.zeros(len(X), np.int64)
    live = np.ones(len(X), bool) if tree.num_leaves > 1 else np.zeros(len(X), bool)
    while live.any():
        at = node[live]
        left = X[live, np.asarray(tree.split_feature)[at]] \
            <= np.asarray(tree.threshold)[at]
        child = np.where(left, np.asarray(tree.left_child)[at],
                         np.asarray(tree.right_child)[at])
        rows = np.flatnonzero(live)
        done = child < 0
        leaf[rows[done]] = ~child[done]
        node[rows[~done]] = child[~done]
        live[rows[done]] = False
    return leaf


def test_wide_table_equals_float64(narrow_chunks):
    bst = _train()
    assert bst._gbdt.spec.chunk_rows == 256

    # the trees against float64 sums over their own split structure
    X, y = _table()
    X64 = X.astype(np.float64)
    score = np.zeros(ROWS)
    assert bst.init_score_value == 0.0
    for tree in bst.trees:
        n = int(tree.num_leaves)
        assert n == 31
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1.0 - p)
        leaf = _leaf_of_rows(tree, X64)
        G = np.bincount(leaf, weights=g, minlength=n)
        H = np.bincount(leaf, weights=h, minlength=n)
        np.testing.assert_array_equal(np.bincount(leaf, minlength=n),
                                      np.asarray(tree.leaf_count)[:n])
        want = -PARAMS["learning_rate"] * G / H
        got = np.asarray(tree.leaf_value, np.float64)[:n]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5)
        score += got[leaf]


def test_wide_table_same_trees_at_the_chunk_of_a_narrow_one(narrow_chunks,
                                                            monkeypatch):
    """Eight chunks of 256 rows against the one 2,048-row chunk the same
    table gets from the rule's own bytes: other chunk partials, so the sums
    differ in their last bits and the trees in nothing else."""
    narrow = _train()
    assert narrow._gbdt.spec.chunk_rows == 256
    monkeypatch.undo()
    one = _train()
    assert one._gbdt.spec.chunk_rows == 2048
    for a, b in zip(narrow.trees, one.trees, strict=True):
        n = int(a.num_leaves)
        assert n == int(b.num_leaves)
        for name in ("split_feature", "threshold_bin", "left_child",
                     "right_child", "leaf_count"):
            np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                          np.asarray(getattr(b, name)), name)
        np.testing.assert_allclose(np.asarray(a.leaf_value)[:n],
                                   np.asarray(b.leaf_value)[:n],
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("by_width", [True, False], ids=["width", "max-chunk"])
def test_pass_counters_by_hand(telemetry, monkeypatch, by_width):
    """One observation a tree; the accumulator's bytes are the chunk matmuls
    the passes launched times the [F, B, S*ch] f32 accumulator, read and
    written once each."""
    if by_width:
        # 48 MiB hold 1,024 rows of 384 columns at 64 bins x 2 bytes
        monkeypatch.setattr(histogram, "_ONEHOT_BYTES_A_CHUNK", 48 << 20)
    # two chunks of 1,024 rows either way, so a compacted pass can run fewer
    # chunks than a streamed one
    bst = _train(PARAMS if by_width else dict(PARAMS, tpu_hist_chunk=1024))
    spec = bst._gbdt.spec
    assert spec.chunk_rows == 1024
    reg = obs.get_registry()
    gauges = obs.snapshot()["gauges"]
    B, S, ch = spec.num_bins_padded, spec.hist_slots, 5      # bf16 hi/lo
    assert B == 64
    acc_bytes = COLS * B * S * ch * 4
    assert gauges["hist.chunk_rows"] == 1024
    assert gauges["hist.onehot_bytes"] == 1024 * COLS * B * 2
    assert gauges["hist.acc_bytes"] == acc_bytes

    names = ("grow.waves", "grow.hist_rows_touched", "grow.hist_chunks",
             "grow.hist_acc_bytes", "grow.scan_slots",
             "grow.scan_slots_pending")
    seen = {n: reg.summary(n).values() for n in names}
    assert all(len(v) == TREES for v in seen.values())
    for waves, touched, chunks, acc, slots, held in zip(
            *(seen[n] for n in names)):
        assert chunks == touched / 1024 and chunks >= 2
        assert acc == chunks * acc_bytes * 2
        assert slots == waves * 2 * S
        assert 1 <= held <= slots
        # 31 leaves: 30 splits, each scanned as a pending leaf and a sibling
        # in the wave after it, the last wave's children never; the root once
        assert held <= 1 + 2 * 30
    events = [e for e in obs.get_tracer().events()
              if e.get("name") == "hist_pass_shape"]
    assert events and events[-1]["args"]["rule"] == (
        "width" if by_width else "max_chunk")
