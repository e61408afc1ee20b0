"""LambdaMART on a synthetic query-grouped table, held to the plain reference
the benchmark's ranking cell uses (``benchmarks/lib/reference_rank.py``:
nothing of the program in it), at a small size on the CPU.

What is held, with each tolerance's reason:
  grad/hess   5e-6 of the query's largest |reference| (the reference's
              ``grad_gap``): float32 pair blocks summed over at most a few
              hundred partners against float64; 1e-7 to 1e-6 read here. The
              pair block computed in bfloat16 reads 4e-3 and more.
  leaf values 2e-4 of max(|ref|, the tree's median |ref|): the program's
              histograms sum float32 gradients in its bf16 hi/lo split; the
              reference sums float32 gradients of its own in float64 (2.8e-6
              to 6.8e-6 read here; leaves valued from the bfloat16 pair block
              3.5e-3 to 5.8e-3).
  counts      exact: integers.
The queries span seven power-of-two length classes and hold a one-doc query, a
query whose labels are all equal, queries one doc past a class (9, 17, 65,
129) and, at the first tree, nothing but tied scores.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.objectives import LambdarankNDCG, default_label_gain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from lib import compare, reference, reference_rank  # noqa: E402

SIZES = np.array([1, 3, 8, 9, 16, 17, 40, 64, 65, 129, 300, 20, 7, 33, 90])
EQUAL_LABELS = 11                       # the query whose labels are all 2
GRAD_TOL, LEAF_TOL = 5e-6, 2e-4
GAINS = np.asarray(default_label_gain())
RANK = Config.from_params({"objective": "lambdarank"})     # sigmoid, max_position
PARAMS = dict(objective="lambdarank", num_leaves=15, max_bin=255, learning_rate=0.1,
              min_data_in_leaf=1, min_sum_hessian_in_leaf=1.0, tree_learner="serial",
              tree_batch=1, metric="none", verbose=-1, device="cpu", tpu_hist_chunk=256)


def _labels(sizes, seed=0):
    rng = np.random.default_rng(seed)
    label = rng.choice(5, size=int(sizes.sum()), p=[.5, .2, .15, .1, .05])
    qb = reference_rank.bounds(sizes)
    if len(sizes) > EQUAL_LABELS:
        label[qb[EQUAL_LABELS]:qb[EQUAL_LABELS + 1]] = 2
    return label.astype(np.float32)


def _objective(sizes, label):
    meta = Metadata(len(label))
    meta.set_label(label)
    meta.set_group(sizes)
    obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
    obj.init(meta, len(label))
    return obj


def _program_gradients(obj, score, label, pad):
    """The objective's grad/hess at ``score``, with ``pad`` rows of the
    grower's chunk padding behind the table, as the step hands them over."""
    padded = np.concatenate([score, np.zeros(pad)]).astype(np.float32)
    lab = np.concatenate([label, np.zeros(pad, np.float32)])
    g, h = jax.jit(obj.gradients)(jnp.asarray(padded)[None, :], jnp.asarray(lab), None)
    return np.asarray(g[0], np.float64), np.asarray(h[0], np.float64)


@pytest.mark.parametrize("scores", ["tied", "distinct"])
def test_gradients_equal_the_float64_reference(scores):
    label = _labels(SIZES)
    n = len(label)
    score = (np.zeros(n) if scores == "tied"
             else np.random.default_rng(1).normal(0.0, 1.0, n))
    obj = _objective(SIZES, label)
    assert len(obj.classes) >= 4
    g, h = _program_gradients(obj, score, label, pad=256 - n % 256)
    assert not g[n:].any() and not h[n:].any()
    queries = np.arange(len(SIZES))
    cfg = obj.config
    gr, hr = reference_rank.sample_gradients(
        score, label, SIZES, queries, gains=GAINS, sigma=cfg.sigmoid,
        max_position=cfg.max_position)
    assert reference_rank.grad_gap(g[:n], gr, SIZES, queries) <= GRAD_TOL
    assert reference_rank.grad_gap(h[:n], hr, SIZES, queries) <= GRAD_TOL
    # no lambda where no pair differs: one doc, or all labels equal
    qb = reference_rank.bounds(SIZES)
    for q in (0, EQUAL_LABELS):
        assert not g[qb[q]:qb[q + 1]].any() and not h[qb[q]:qb[q + 1]].any()


@pytest.mark.parametrize("scores", ["tied", "distinct"])
def test_device_reference_agrees_and_its_bf16_control_does_not(scores):
    """Form (b) of the reference (every doc, float32, no sort) agrees with
    form (a); the same block in bfloat16 is refused by the tolerance."""
    label = _labels(SIZES)
    n = len(label)
    score = (np.zeros(n) if scores == "tied"
             else np.random.default_rng(2).normal(0.0, 1.0, n))
    queries = np.arange(len(SIZES))
    kw = dict(gains=GAINS, sigma=RANK.sigmoid, max_position=RANK.max_position)
    gr, hr = reference_rank.sample_gradients(score, label, SIZES, queries, **kw)
    gd, hd = reference_rank.DeviceGradients(SIZES, label, **kw)(score)
    assert reference_rank.grad_gap(gd, gr, SIZES, queries) <= GRAD_TOL
    assert reference_rank.grad_gap(hd, hr, SIZES, queries) <= GRAD_TOL
    gb, hb = reference_rank.DeviceGradients(SIZES, label, precision="bf16", **kw)(score)
    assert reference_rank.grad_gap(gb, gr, SIZES, queries) > 10 * GRAD_TOL
    assert reference_rank.grad_gap(hb, hr, SIZES, queries) > 10 * GRAD_TOL


def test_pair_counters_equal_the_host_count():
    obs.reset_for_tests()
    label = _labels(SIZES)
    obj = _objective(SIZES, label)
    qb = reference_rank.bounds(SIZES)
    pairs = sum(int(np.sum(label[lo:hi, None] > label[None, lo:hi]))
                for lo, hi in zip(qb[:-1], qb[1:]))
    slots = 0
    for m in {max(8, 1 << int(np.ceil(np.log2(s)))) for s in SIZES}:
        queries = sum(max(8, 1 << int(np.ceil(np.log2(s)))) == m for s in SIZES)
        chunk = max(1, LambdarankNDCG.QUERY_CHUNK_BUDGET // (m * m))
        slots += -(-queries // chunk) * chunk * m * m
    snap = obs.get_registry().snapshot()
    assert obj.pairs == pairs and snap["counters"]["rank.pairs"] == pairs
    assert obj.pair_slots == slots and snap["counters"]["rank.pair_slots"] == slots
    assert snap["gauges"]["rank.queries"] == len(SIZES)
    assert snap["gauges"]["rank.length_classes"] == len(obj.classes) == 7
    obs.reset_for_tests()


def _table(seed=3, queries=60):
    rng = np.random.default_rng(seed)
    sizes = np.clip(np.rint(rng.lognormal(np.log(40.0), 1.0, queries)), 1, 300).astype(np.int64)
    n = int(sizes.sum())
    X = rng.random((n, 8)).astype(np.float32)
    z = 2.0 * X[:, 0] - X[:, 3] + np.repeat(rng.normal(0, 0.5, queries), sizes) \
        + rng.normal(0, 0.5, n)
    label = np.searchsorted(np.quantile(z, [0.6, 0.8, 0.92, 0.98]), z).astype(np.float32)
    return X, label, sizes


def test_three_trees_value_as_the_reference_values_them():
    """``lgb.train`` three trees; the reference routes every row through each
    tree by raw value, takes its own gradients (form b) at its own running
    score (tied at the first tree) and values the leaves."""
    X, label, sizes = _table()
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=label, group=sizes), num_boost_round=3,
                    keep_training_booster=True)
    gbdt = bst._gbdt
    assert gbdt.num_data_padded > len(label)           # chunk padding behind the rows
    bst._ensure_finalized()
    trees = [{**{k: np.asarray(getattr(t, k)) for k in (
        "split_feature", "threshold", "decision_type", "left_child", "right_child",
        "split_gain", "internal_count", "leaf_value", "leaf_count")},
        "num_leaves": int(t.num_leaves)} for t in bst.trees]
    assert all(t["num_leaves"] > 1 for t in trees)
    grads = reference_rank.DeviceGradients(sizes, label, gains=GAINS, sigma=RANK.sigmoid,
                                           max_position=RANK.max_position)
    ref = reference_rank.value_trees(X, trees, grads, learning_rate=0.1, lambda_l2=0.0,
                                     init_score=0.0, sample=np.arange(len(label)))
    for t, tree in enumerate(trees):
        L = tree["num_leaves"]
        assert np.array_equal(tree["leaf_count"][:L], ref["leaf_count"][t])
        assert np.array_equal(tree["internal_count"][:L - 1], ref["node_count"][t])
        worst, _ = compare.gaps(tree["leaf_value"][:L].astype(np.float64), ref["leaf_value"][t])
        assert worst <= LEAF_TOL, (t, worst)
    walked = reference.walk(X, trees, 0.0)
    np.testing.assert_allclose(bst.predict(X, raw_score=True), walked, atol=2e-6)


def test_the_objective_travels_as_a_step_argument():
    """The query layout enters the step as an argument: the lowered step
    holds no constant of the table's size, and the pre-flight counts it."""
    X, label, sizes = _table(queries=20)
    bst = lgb.Booster(params=PARAMS, train_set=lgb.Dataset(X, label=label, group=sizes))
    gbdt = bst._gbdt
    consts, valid_Xb, valid_scores = gbdt._dispatch_prep(0.1)
    assert consts["objective"] is gbdt.objective.device_arrays
    text = gbdt._make_step().lower(
        consts, valid_Xb, gbdt.score, valid_scores, gbdt.bag_mask, gbdt._rng_key,
        gbdt._iter_dev, gbdt._shrink_cache[1]).as_text()
    # an int32 array of every row would be 8 hex digits a row
    blobs = re.findall(r'dense<"0x([0-9A-F]*)"', text)
    assert max(map(len, blobs), default=0) < 2 * len(label)
    from lightgbm_tpu.observability.memory import hbm_preflight
    assert hbm_preflight(gbdt)["components"]["objective"] == gbdt.objective.device_bytes() > 0
    assert obs.get_registry().snapshot()["gauges"]["setup.booster_objective_init_s"] >= 0.0


def test_an_elementwise_objective_adds_no_preflight_term():
    X, label, _ = _table(queries=10)
    params = dict(PARAMS, objective="regression")
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=label))
    from lightgbm_tpu.observability.memory import hbm_preflight
    assert bst._gbdt.objective.device_arrays is None
    assert hbm_preflight(bst._gbdt)["components"]["objective"] == 0
