"""Boosting-mode tests: dart / goss / rf + custom objective
(reference: test_engine.py dart at :56, sklearn dart at :106)."""
import numpy as np
import pytest
from sklearn.datasets import load_breast_cancer, make_regression
from sklearn.metrics import log_loss, mean_squared_error, roc_auc_score

import lightgbm_tpu as lgb


@pytest.mark.slow
def test_dart():
    X, y = load_breast_cancer(return_X_y=True)
    params = {"objective": "binary", "boosting_type": "dart", "verbose": -1,
              "drop_rate": 0.2, "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=40, verbose_eval=False)
    ll = log_loss(y, bst.predict(X))
    assert ll < 0.3


@pytest.mark.slow
def test_dart_xgboost_mode():
    X, y = make_regression(n_samples=600, n_features=8, noise=5.0, random_state=1)
    params = {"objective": "regression", "boosting_type": "dart", "verbose": -1,
              "xgboost_dart_mode": True, "uniform_drop": True}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    assert mean_squared_error(y, bst.predict(X)) < 0.6 * np.var(y)


def test_goss():
    X, y = load_breast_cancer(return_X_y=True)
    params = {"objective": "binary", "boosting_type": "goss", "verbose": -1,
              "top_rate": 0.2, "other_rate": 0.1, "learning_rate": 0.1}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=40, verbose_eval=False)
    auc = roc_auc_score(y, bst.predict(X))
    assert auc > 0.99  # train auc


def test_rf():
    X, y = load_breast_cancer(return_X_y=True)
    params = {"objective": "binary", "boosting_type": "rf", "verbose": -1,
              "bagging_fraction": 0.6, "bagging_freq": 1, "feature_fraction": 0.7}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
    pred = bst.predict(X)
    # rf predictions are averaged probabilities already
    assert 0.0 <= pred.min() and pred.max() <= 1.0
    assert roc_auc_score(y, pred) > 0.98


@pytest.mark.slow
def test_custom_objective_fobj():
    X, y = make_regression(n_samples=500, n_features=6, noise=3.0, random_state=2)

    def l2_fobj(preds, dataset):
        grad = preds - y
        hess = np.ones_like(preds)
        return grad, hess

    params = {"objective": "none", "verbose": -1, "boost_from_average": False}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=40, fobj=l2_fobj, verbose_eval=False)
    assert mean_squared_error(y, bst.predict(X)) < 0.3 * np.var(y)
