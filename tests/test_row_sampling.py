"""Row sampling as the histogram's row set (ISSUE 36): GOSS, bagging and rf
hand the grower their 0/1 mask with ``sampled=True``; a row counts as pending
for the HISTOGRAM only where it is included, routing still moves every row
and every row is scored. The program is held to the plain reference the
benchmark's GOSS cell uses (``benchmarks/lib/reference_goss.py``: NumPy,
float64, nothing of the program in it), at a small size on the CPU.

Tolerances, each with its reason:
  counts          exact: integers.
  top set         exact outside the reference's tie band (float32 against
                  float64 products at the threshold).
  leaf values     2e-5 of max(|ref|, the tree's median |ref|): a sampled
                  tree takes its leaf values from the rows' own float32 sums
                  (``grower._leaf_values_from_rows``); this data reads 3.4e-7.
                  From the parent's histogram differences it read 5.6e-5 here
                  and 7.8e-3 in one leaf of the chip's cell.
  gains           1e-3: differences of squares of the histograms' float32
                  sums (9.5e-6 read).
  all-row scores  2e-6 absolute on scores of order 1: float32 adds (7e-8 read).
A tree without its amplification reads 0.05 and more in leaf values, the
bfloat16 control 1.9e-3: the limits sit between.
"""
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import grower
from lightgbm_tpu import observability as obs
from lightgbm_tpu.boosting import goss as goss_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from lib import compare, reference, reference_goss  # noqa: E402

N, F = 20000, 12
TOP, OTHER = 0.2, 0.1
BASE = dict(objective="binary", num_leaves=31, learning_rate=0.1,
            min_data_in_leaf=20, verbose=-1, metric="none", device="cpu",
            seed=5, tree_batch=1, tpu_hist_chunk=256)
GOSS = dict(BASE, boosting="goss", top_rate=TOP, other_rate=OTHER)
BAG = dict(BASE, bagging_fraction=0.5, bagging_freq=1)
SEM = dict(learning_rate=0.1, lambda_l2=0.0, top_rate=TOP, other_rate=OTHER)
LEAF_TOL, GAIN_TOL, SCORE_TOL = 2e-5, 1e-3, 2e-6


def _train_job():
    spec = importlib.util.spec_from_file_location(
        "job_train_for_sampling", os.path.join(BENCH, "jobs", "train.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _data(seed=11):
    rng = np.random.RandomState(seed)
    X = rng.rand(N, F).astype(np.float32)
    logit = 2.0 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3] - 0.6
    y = (logit + 0.5 * rng.randn(N) > 0).astype(np.float32)
    return X, y


@pytest.fixture
def registry():
    obs.reset_for_tests()
    yield obs.get_registry()
    obs.reset_for_tests()


def _booster(params, X, y, rounds):
    bst = lgb.Booster(params=dict(params), train_set=lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update()
    return bst


def _sampled_step(params=GOSS, before=11):
    """A booster past GOSS's unsampled iterations, then ONE more tree: the
    score before it, the step's mask, the tree as the reference sees it,
    the score of every row after it."""
    X, y = _data()
    bst = _booster(params, X, y, before)
    g = bst._gbdt
    score_before = np.asarray(jax.device_get(g.score[0]))[:N]
    bst.update()
    included = np.asarray(jax.device_get(g.bag_mask))[:N] > 0
    score_after = np.asarray(jax.device_get(g.score[0]), np.float64)[:N]
    bst._ensure_finalized()
    return X, y, bst, score_before, included, _train_job().tree_dict(bst.trees[-1]), score_after


def _value(X, y, tree, score_before, included, **mode):
    return reference_goss.value_tree(X, y, tree, score_before, included,
                                     sample=np.arange(N), **SEM, **mode)


def _gaps(tree, score_after, ref):
    L = tree["num_leaves"]
    return {
        "count": int(np.sum(tree["leaf_count"][:L] != ref["leaf_count"][0])
                     + np.sum(tree["internal_count"][:L - 1] != ref["node_count"][0])),
        "leaf": compare.gaps(np.asarray(tree["leaf_value"][:L], np.float64),
                             ref["leaf_value"][0])[0],
        "gain": compare.gaps(np.asarray(tree["split_gain"][:L - 1], np.float64),
                             ref["gain"][0])[0],
        "score": float(np.max(np.abs(score_after - ref["sample_score"][0])))}


# ------------------------------------------- (a) the program and the reference

@pytest.fixture(scope="module")
def goss_step():
    obs.reset_for_tests()
    out = _sampled_step()
    obs.reset_for_tests()
    return out


def test_goss_tree_is_what_the_plain_reference_values(goss_step):
    X, y, _bst, score_before, included, tree, score_after = goss_step
    ref = _value(X, y, tree, score_before, included)
    top_k, other_k, amplify = reference_goss.counts(N, TOP, OTHER)
    s = ref["sample"]
    assert (top_k, other_k, amplify) == (4000, 2000, 8.0)
    assert s["top_missed"] == 0 and s["other_count_gap"] < 1.0
    assert s["rows_top"] + s["rows_other"] == int(included.sum())
    assert abs(s["rows_top"] - top_k) <= s["band_rows"]
    gaps = _gaps(tree, score_after, ref)
    assert gaps["count"] == 0
    assert gaps["leaf"] < LEAF_TOL and gaps["gain"] < GAIN_TOL, gaps
    # every row is scored, the out-of-sample two thirds among them
    assert not included.all() and gaps["score"] < SCORE_TOL, gaps


def test_the_steps_own_count_is_the_masks(registry):
    _X, _y, _bst, _sb, included, _tree, _sa = _sampled_step()
    top, other, rows_in = (registry.summary("sample." + k).values()
                           for k in ("rows_top", "rows_other", "rows_in"))
    assert top[:10] == [0.0] * 10 and rows_in[:10] == [float(N)] * 10
    assert top[10:] == [4000.0, 4000.0]
    assert rows_in[-1] == float(included.sum()) == top[-1] + other[-1]
    assert registry.snapshot()["gauges"]["sample.amplify"] == 8.0


@pytest.mark.parametrize("fault,number,floor", [
    (dict(amplified=False), "leaf", 0.05),
    (dict(score_out_of_sample=False), "score", 1e-3),
    (dict(precision="bf16"), "leaf", 50 * LEAF_TOL),
    (dict(rows_kept=0.5), "leaf", 0.05),
])
def test_planted_value_faults_read_over_the_tolerances(goss_step, fault, number,
                                                       floor):
    """The reference in the program's place, with the fault planted: a tree
    without its amplification, out-of-sample rows left unscored, g and h
    rounded to bfloat16 (the control), half of every chunk left out."""
    X, y, _bst, score_before, included, tree, _sa = goss_step
    sound = _value(X, y, tree, score_before, included)
    faulty = _value(X, y, tree, score_before, included, **fault)
    dressed = dict(tree, leaf_value=faulty["leaf_value"][0],
                   split_gain=faulty["gain"][0], leaf_count=faulty["leaf_count"][0],
                   internal_count=faulty["node_count"][0])
    gaps = _gaps(dressed, faulty["sample_score"][0], sound)
    assert gaps[number] > floor, gaps


@pytest.mark.parametrize("fault,number", [(dict(top_by="g"), "top_missed"),
                                          (dict(kept=0.5), "top_missed"),
                                          (dict(kept=0.5), "other_count_gap")])
def test_planted_sample_faults_read_over_the_limits(fault, number):
    """A top set taken by |g| alone, and half of the sample dropped: masks no
    sound program makes, read by the reference's own two numbers. Scores
    spread wide enough that |g*h| = g^2 (1 - |g|) is not monotone in |g|
    (rows fitted worst of all have the largest |g| and a small hessian), as
    in a table with few positives."""
    rng = np.random.RandomState(2)
    score, y = 2.5 * rng.randn(N), (rng.rand(N) < 0.2).astype(np.float64)
    g, h = reference_goss.gradients(score, y)

    def read(**planted):
        return reference_goss.read_sample(
            np.abs(g * h), reference_goss.draw_sample(
                score, y, top_rate=TOP, other_rate=OTHER, seed=3, **planted),
            TOP, OTHER)

    sound = read()
    assert sound["top_missed"] == 0 and sound["other_count_gap"] < 1.0
    assert read(**fault)[number] > (1.0 if number == "other_count_gap" else 100)


def test_a_program_without_amplification_fails_the_reference(monkeypatch):
    """The fault planted in the PROGRAM: the drawn rows keep weight 1."""
    select = goss_module.goss_select
    monkeypatch.setattr(
        goss_module, "goss_select",
        lambda *a: select(*a)._replace(scale=jax.numpy.ones_like(a[0])))
    X, y, _bst, score_before, included, tree, score_after = _sampled_step()
    gaps = _gaps(tree, score_after, _value(X, y, tree, score_before, included))
    assert gaps["leaf"] > 0.05 and gaps["count"] == 0


def test_top_set_is_exact_with_ties_to_the_lower_row():
    """``goss_select`` against a sort: exactly ``top_k`` rows, a tie group
    that straddles the threshold cut in row order, padding never taken."""
    rng = np.random.RandomState(0)
    w = rng.rand(10000).astype(np.float32)
    w[:700] = np.sort(w)[-1500]                 # 700 ties at the threshold
    valid = np.ones(10000, bool)
    valid[-100:] = False
    u = rng.rand(10000).astype(np.float32)
    n, top_k, other_k = 9900, 1980, 990
    s = jax.jit(lambda w, v, u: goss_module.goss_select(w, v, u, n, top_k, other_k))(
        w, valid, u)
    order = np.lexsort((np.arange(10000), -np.where(valid, w, -1.0)))
    want = np.zeros(10000, bool)
    want[order[:top_k]] = True
    assert np.array_equal(np.asarray(s.is_top), want)
    is_other = np.asarray(s.is_other)
    assert not (is_other & want).any() and not is_other[~valid].any()
    assert np.array_equal(is_other, valid & ~want & (u < other_k / (n - top_k)))
    assert set(np.unique(np.asarray(s.scale))) == {1.0, 8.0}


# ----------------------- (b) the sample is what the histogram passes touch

@pytest.mark.parametrize("params,share,first,extra", [
    (GOSS, TOP + OTHER, 10, {}),
    # a bag of half the rows compacts its root where the threshold is above
    # a half (this table's own is 0.38: such a root streams)
    (BAG, 0.5, 0, dict(tpu_compact_frac=0.9))])
def test_histogram_rows_follow_the_included_share(registry, params, share, first,
                                                  extra):
    X, y = _data()
    rounds = first + 4
    params = dict(params, **extra)
    full = _booster(dict(BASE, **extra), X, y, rounds)
    full._ensure_finalized()
    touched_full = registry.summary("grow.hist_rows_touched").values()
    obs.reset_for_tests()
    bst = _booster(params, X, y, rounds)
    bst._ensure_finalized()
    reg = obs.get_registry()
    touched = reg.summary("grow.hist_rows_touched").values()
    active = reg.summary("grow.hist_rows_active").values()
    for t in range(first, rounds):
        assert touched[t] <= (share + 0.05) * touched_full[t], (t, touched, touched_full)
        # every pass of a sampled tree is compacted: only chunk tails idle
        assert active[t] > 0.85 * touched[t]
    assert reg.summary("grow.stream_passes").values()[first:] == [0.0] * 4
    # out-of-sample rows are scored: the resident score of EVERY row is the
    # walk of the trees
    resident = np.asarray(jax.device_get(bst._gbdt.score[0]), np.float64)[:N]
    np.testing.assert_allclose(resident, bst.predict(X, raw_score=True),
                               atol=5e-6, rtol=0)
    walked = reference.walk(X, [_train_job().tree_dict(t) for t in bst.trees], 0.0)
    np.testing.assert_allclose(resident, walked, atol=5e-6, rtol=0)


# --------------------- (c) a step that samples nothing is the step it was

def test_an_unsampled_step_carries_no_sample(registry, monkeypatch):
    """Whether the step samples is static: a booster with bagging configured
    but off (fraction 1) hands the grower ``sampled=False``, its record has
    no sample, its step returns as many arrays as plain gbdt's (a sampling
    step returns three more), and it grows plain gbdt's trees to the bit."""
    seen = []
    grow = grower.grow_tree

    def spy(*a, **k):
        seen.append(k.get("sampled"))
        return grow(*a, **k)

    from lightgbm_tpu.boosting import gbdt as gbdt_module
    monkeypatch.setattr(gbdt_module, "grow_tree", spy)
    X, y = _data()
    texts, leaves = {}, {}
    for name, params in (("plain", BASE),
                         ("off", dict(BASE, bagging_fraction=1.0, bagging_freq=1)),
                         ("bagged", BAG)):
        bst = _booster(params, X, y, 3)
        bst._ensure_finalized()
        texts[name] = [(t.leaf_value.tobytes(), t.threshold.tobytes(),
                        t.split_feature.tobytes()) for t in bst.trees]
        rec = bst._gbdt._grow_records[-1]
        leaves[name] = len(jax.tree.leaves(rec))
        assert (rec.sample is None) == (name != "bagged")
    assert seen == [False, False, True]
    assert texts["plain"] == texts["off"] != texts["bagged"]
    assert leaves["plain"] == leaves["off"] == leaves["bagged"] - 3
    # only the bagged booster's three trees published a sample
    assert len(registry.summary("sample.rows_in").values()) == 3


def test_unsampled_grower_traces_the_same_program():
    """``grow_tree(sampled=False)`` is the jaxpr of ``grow_tree`` as it was
    called before the argument existed; ``sampled=True`` differs by the
    selects that take out-of-sample rows out of the slots and by the leaf
    values taken again from the rows (one more scan): some hundred lines."""
    from lightgbm_tpu.grower import GrowerSpec
    X, y = _data()
    g = lgb.Booster(params=dict(BASE), train_set=lgb.Dataset(X, label=y))._gbdt
    args = (g.Xb, g.label, g.label, g.pad_mask, g.feature_ok_base, g.is_cat,
            g.num_bins, g.missing_code, g.default_bin)
    assert isinstance(g.spec, GrowerSpec)

    def traced(**kw):
        return str(jax.make_jaxpr(
            lambda *a: grower.grow_tree(*a, g.spec, **kw))(*args))

    plain, off, on = traced(), traced(sampled=False), traced(sampled=True)
    assert plain == off != on
    assert 0 < len(on.splitlines()) - len(off.splitlines()) < 200


# ----------------------------- (d) each shard samples and counts its own rows

def test_goss_under_data_parallel_samples_per_shard(registry):
    D = 4
    X, y = _data()
    bst = _booster(dict(GOSS, tree_learner="data", num_machines=D), X, y, 13)
    g = bst._gbdt
    assert g.pctx.strategy == "data" and g.pctx.num_devices == D
    bst._ensure_finalized()
    rows_in = registry.summary("sample.rows_in").values()
    top = registry.summary("sample.rows_top").values()
    assert rows_in[:10] == [float(N)] * 10
    # each shard takes floor(0.2 n) of ITS rows, then a binomial draw
    width = 5 * np.sqrt(N * 0.8 * 0.125 * 0.875) + D
    for t in (10, 11, 12):
        assert abs(top[t] - TOP * N) <= D
        assert abs(rows_in[t] - (TOP + OTHER) * N) < width, rows_in
    resident = np.asarray(jax.device_get(g.score[0]), np.float64)[:N]
    np.testing.assert_allclose(resident, bst.predict(X, raw_score=True),
                               atol=5e-6, rtol=0)
    # the sampled trees' passes ran over the shards' samples
    touched = registry.summary("grow.hist_rows_touched").values()
    assert max(touched[10:]) < 0.5 * min(touched[:10])
