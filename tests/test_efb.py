"""EFB exclusive feature bundling tests
(reference: Dataset::Construct FindGroups/FastFeatureBundling,
src/io/dataset.cpp:66-295; encoding feature_group.h:30-52)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.efb import plan_bundles


def _mixed_sparse_data(n=2000, dense=4, flag_groups=3, flags_per_group=20,
                       seed=3):
    """Few dense features + many mutually-exclusive binary flags (the one-hot
    regime EFB was built for). Flags within a group are exclusive; flags in
    different groups conflict on every row."""
    rng = np.random.RandomState(seed)
    Xd = rng.rand(n, dense)
    flags = np.zeros((n, flag_groups * flags_per_group))
    picks = rng.randint(0, flags_per_group, size=(n, flag_groups))
    for g in range(flag_groups):
        flags[np.arange(n), g * flags_per_group + picks[:, g]] = 1.0
    X = np.concatenate([Xd, flags], axis=1)
    y = (Xd[:, 0] + 0.3 * (picks[:, 0] > flags_per_group // 2)
         + 0.1 * rng.randn(n) > 0.65).astype(np.float64)
    return X, y, dense, flag_groups


def _constructed(X, y, **params):
    ds = lgb.Dataset(X, label=y)
    ds.construct(Config.from_params(dict(verbose=-1, **params)))
    return ds.constructed


def test_plan_bundles_exclusive_flags():
    X, y, dense, flag_groups = _mixed_sparse_data()
    cd = _constructed(X, y)
    meta = cd.feature_meta_arrays()
    plan = plan_bundles(cd.X_binned, meta["num_bins"].astype(np.int64),
                        meta["default_bin"].astype(np.int64), cd.config)
    assert plan is not None
    # 64 features collapse to ~dense singletons + ~one bundle per flag group
    assert plan.num_groups <= dense + flag_groups + 2, plan.num_groups
    # zero-conflict data: decode must round-trip every (row, feature) bin
    for f in range(cd.num_features):
        c = plan.X_bundled[:, plan.col[f]].astype(np.int64)
        in_rng = (c >= plan.lo[f]) & (c < plan.hi[f])
        dec = np.where(in_rng, c - plan.off[f], meta["default_bin"][f])
        np.testing.assert_array_equal(dec, cd.X_binned[:, f],
                                      err_msg=f"feature {f}")


def test_unpack_map_consistency():
    X, y, _, _ = _mixed_sparse_data()
    cd = _constructed(X, y)
    meta = cd.feature_meta_arrays()
    plan = plan_bundles(cd.X_binned, meta["num_bins"].astype(np.int64),
                        meta["default_bin"].astype(np.int64), cd.config)
    assert plan is not None
    for f in range(cd.num_features):
        nb = int(meta["num_bins"][f])
        db = int(meta["default_bin"][f])
        for b in range(nb):
            ub = plan.unpack_bin[f, b]
            if b == db:
                assert ub == -1            # always reconstructed (FixHistogram)
            elif ub >= 0:
                # unpack slot must be inside this feature's code range and
                # decode back to b
                assert plan.lo[f] <= ub < plan.hi[f]
                assert ub - plan.off[f] == b


@pytest.mark.slow
def test_bundled_training_matches_unbundled():
    X, y, _, _ = _mixed_sparse_data()
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                  device="cpu", verbose=-1)
    b_on = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10,
                     keep_training_booster=True, verbose_eval=False)
    assert b_on._gbdt.bundle is not None, "EFB should trigger on this data"
    assert b_on._gbdt.Xb.shape[1] < X.shape[1] // 2
    b_off = lgb.train(dict(params, enable_bundle=False),
                      lgb.Dataset(X, label=y), num_boost_round=10,
                      verbose_eval=False)
    p_on, p_off = b_on.predict(X), b_off.predict(X)
    # zero-conflict bundles reproduce the same histograms up to the
    # default-bin reconstruction rounding -> near-identical models
    assert np.mean((p_on > 0.5) == (y > 0.5)) > 0.85
    np.testing.assert_allclose(p_on, p_off, rtol=0.0, atol=5e-3)


def test_dense_data_skips_bundling():
    rng = np.random.RandomState(0)
    X = rng.rand(500, 8)
    y = (X[:, 0] > 0.5).astype(float)
    bst = lgb.train(dict(objective="binary", verbose=-1, device="cpu"),
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    keep_training_booster=True, verbose_eval=False)
    assert bst._gbdt.bundle is None


def test_conflict_rate_allows_near_exclusive():
    """max_conflict_rate > 0 admits features that collide on a few rows
    (reference max_error_cnt, dataset.cpp:152)."""
    rng = np.random.RandomState(1)
    n, F = 3000, 30
    X = np.zeros((n, F))
    picks = rng.randint(0, F, size=n)
    X[np.arange(n), picks] = rng.rand(n) + 0.5
    # ~2% fully-dense rows -> EVERY feature pair conflicts on ~2% of rows
    dense_rows = rng.choice(n, n // 50, replace=False)
    X[dense_rows] = rng.rand(len(dense_rows), F) + 0.5
    y = (picks % 2).astype(float)
    cd0 = _constructed(X, y, max_conflict_rate=0.0)
    meta = cd0.feature_meta_arrays()
    p0 = plan_bundles(cd0.X_binned, meta["num_bins"].astype(np.int64),
                      meta["default_bin"].astype(np.int64), cd0.config)
    cd1 = _constructed(X, y, max_conflict_rate=0.05)
    p1 = plan_bundles(cd1.X_binned, meta["num_bins"].astype(np.int64),
                      meta["default_bin"].astype(np.int64), cd1.config)
    n0 = p0.num_groups if p0 is not None else F
    assert p1 is not None
    assert p1.num_groups < n0


# ---- the bin rule: no plan, and no planning sample, where no two features
#      can share a group by their bins alone (efb.no_pair_fits) ----

def _sample_rows_gauge():
    from lightgbm_tpu import observability as obs
    return obs.get_registry().gauge("efb.sample_rows").value


@pytest.mark.parametrize("ingest", ["host", "device"])
def test_wide_bins_draw_no_planning_sample(monkeypatch, ingest):
    """Every column 255 bins: a booster over a materialised (host) or a
    deferred (device ingest) dataset ends with no bundle and never draws,
    bins or reads the planning sample."""
    from lightgbm_tpu import efb
    from lightgbm_tpu.dataset import ConstructedDataset

    def refuse(*a, **k):
        raise AssertionError("the planning sample was drawn")

    rng = np.random.RandomState(0)
    n = 3000
    X = rng.rand(n, 5).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    params = dict(objective="binary", verbose=-1, max_bin=255,
                  tpu_ingest=ingest)
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct(Config.from_params(params))
    cd = ds.constructed
    meta = cd.feature_meta_arrays()
    assert cd.deferred == (ingest == "device")
    assert efb.no_pair_fits(meta["num_bins"], meta["default_bin"])

    # the checkpoint fingerprint reads a strided sample of a deferred
    # dataset through bin_rows; any other read of it is the planning sample
    stride = max(1, n // 256)
    real_bin_rows = ConstructedDataset.bin_rows

    def fingerprint_rows_only(self, rows):
        assert np.array_equal(rows, np.arange(0, n, stride)), len(rows)
        return real_bin_rows(self, rows)

    monkeypatch.setattr(efb, "sample_rows", refuse)
    monkeypatch.setattr(efb, "sample_row_indices", refuse)
    monkeypatch.setattr(ConstructedDataset, "bin_rows", fingerprint_rows_only)
    bst = lgb.Booster(params=params, train_set=ds)
    assert bst._gbdt.bundle is None
    assert _sample_rows_gauge() == 0


def _groups_without_rule(sample, num_bins, default_bin, config, num_data):
    """plan_bundles' grouping with the bin rule left out: both orders
    through _find_groups, None where the better one is all singletons."""
    from lightgbm_tpu.efb import _find_groups
    S, F = sample.shape
    masks = sample != default_bin[None, :]
    counts = np.count_nonzero(masks, axis=0)
    nbins_eff = num_bins - (default_bin == 0).astype(np.int64)
    args = (nbins_eff, int(S * config.max_conflict_rate),
            0.95 * config.min_data_in_leaf / num_data * S, num_data, 256)
    g1 = _find_groups(masks, counts, np.arange(F), *args)
    g2 = _find_groups(masks, counts, np.argsort(-counts, kind="stable"),
                      *args)
    groups = g2 if len(g2) < len(g1) else g1
    return None if len(groups) >= F else groups


def _random_bins(rng, F):
    """Bins of F features, a third of the draws each: from narrow to wide,
    at the bin rule's boundary, past it."""
    choices = [[2, 3, 8, 16, 64, 127, 128, 129, 200, 255, 256],
               [127, 128, 129],
               [129, 200, 255, 256]][rng.randint(3)]
    num_bins = rng.choice(choices, size=F).astype(np.int64)
    default_bin = np.where(rng.rand(F) < 0.6, 0,
                           rng.randint(0, 1 << 20, size=F) % num_bins)
    return num_bins, default_bin.astype(np.int64)


def _random_sample(rng, num_bins, default_bin, S):
    """Rows at the default bin but for a per-feature share drawn from
    0-40%, whose bins are drawn uniformly off the default."""
    F = len(num_bins)
    sample = np.tile(default_bin, (S, 1))
    for f in range(F):
        if num_bins[f] < 2:
            continue
        on = rng.rand(S) < rng.uniform(0.0, 0.4)
        off = rng.randint(1, num_bins[f], size=int(on.sum()))
        sample[on, f] = (default_bin[f] + off) % num_bins[f]
    return sample


# (num_bins, default_bin) pairs on both sides of the boundary: 1 + a + b is
# 255 and 256 (a pair still fits: the rule must not fire) and 257 (none does)
_BOUNDARY = {
    "sum255": ([128, 128], [0, 0]),
    "sum256": ([128, 128], [0, 5]),
    "sum257": ([129, 128], [5, 0]),
}


@pytest.mark.parametrize("case", [f"seed{s}" for s in range(12)]
                         + sorted(_BOUNDARY))
def test_bin_rule_is_exact(case):
    """plan_bundles with the bin rule returns the groups the grouping
    without it returns, and None exactly where that grouping is all
    singletons; the rule fires only where it would be."""
    cfg = Config.from_params(dict(verbose=-1, min_data_in_leaf=1,
                                  max_conflict_rate=0.0))
    if case in _BOUNDARY:
        rng = np.random.RandomState(99)
        nb, db = _BOUNDARY[case]
        num_bins, default_bin = np.int64(nb), np.int64(db)
        S = 400
        # two exclusive features: the pair bundles wherever its bins fit
        sample = np.tile(default_bin, (S, 1))
        half = rng.rand(S) < 0.5
        sample[half, 0] = (default_bin[0] + 1) % num_bins[0]
        sample[~half, 1] = (default_bin[1] + 1) % num_bins[1]
    else:
        rng = np.random.RandomState(int(case[4:]))
        F = rng.randint(2, 14)
        num_bins, default_bin = _random_bins(rng, F)
        S = rng.randint(50, 400)
        sample = _random_sample(rng, num_bins, default_bin, S)
    from lightgbm_tpu.efb import no_pair_fits
    want = _groups_without_rule(sample, num_bins, default_bin, cfg, S)
    got = plan_bundles(sample, num_bins, default_bin, cfg)
    if no_pair_fits(num_bins, default_bin):
        assert want is None
    if want is None:
        assert got is None
    else:
        assert got is not None and got.groups == want
    if case in _BOUNDARY:
        assert (got is None) == (case == "sum257")
        assert no_pair_fits(num_bins, default_bin) == (case == "sum257")


def test_bundling_table_still_draws_its_sample():
    """A table that can bundle plans from its sample as before: the
    booster bundles, and the gauge counts the sample's rows."""
    X, y, _, _ = _mixed_sparse_data()
    params = dict(objective="binary", verbose=-1)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    assert bst._gbdt.bundle is not None
    assert _sample_rows_gauge() == X.shape[0]
