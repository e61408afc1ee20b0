"""The routing table keyed by the wave's split ordinal (PR 33).

A wave splits at most ``hist_slots`` leaves, so ``_apply_wave_splits`` hands
the routing pass one table row an ORDINAL with the split leaf as its key, and
``_route_rows`` resolves a row by one ``[N, S]`` match (``keyed_lookup``)
where it looked its leaf up in a table over all ``L + 1`` leaves through a
256-wide one-hot. Routing is exact, so everything here is held to the bit:

- ``keyed_lookup`` against a plain ``table[idx]`` gather over the widths the
  program uses (6 | 8 | 13 columns), values up to 2^24 - 1, rows that match
  nothing, unused ordinals (key -1) and a leaf id equal to L;
- whole trees (model text and the leaf of every row) against the same
  booster grown with the lookup replaced by that gather, for every consumer
  of the table: serial, categorical, EFB native and unpacked,
  ``tpu_row_compact=false``, streamed residency, ``tree_learner=data``;
- the width the table has in each of them (gauge ``route.table_cols``).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import grower
from lightgbm_tpu.ops.histogram import keyed_lookup


def _gather_lookup(idx, keys, table):
    """The oracle: scatter the S live rows into a table over every id a row
    can carry (ids run to L <= 1024 here; -1 keys are dropped), then a plain
    per-row gather. No one-hot, no matmul."""
    T = 1025
    full = jnp.zeros((T, table.shape[1]), table.dtype).at[
        jnp.where(keys >= 0, keys, T)].set(table, mode="drop")
    return full[idx]


# ------------------------------------------------------- the lookup alone

@pytest.mark.parametrize("C", [6, 8, 13])
@pytest.mark.parametrize("S", [1, 4, 25, 127])
def test_keyed_lookup_matches_a_plain_gather(S, C):
    rng = np.random.RandomState(S * 100 + C)
    L = 255
    live = max(1, S - S // 3)                    # the last ordinals unused
    keys = np.full(S, -1, np.int32)
    keys[:live] = rng.choice(L, size=live, replace=False)
    table = rng.randint(0, 1 << 24, size=(S, C)).astype(np.int32)
    table[0, :] = (1 << 24) - 1                  # the largest exact value
    table[live:] = rng.randint(0, 1 << 24, size=(S - live, C))  # never read
    # every leaf id, the scratch id L, and many rows of unsplit leaves
    idx = np.concatenate([np.arange(L + 1), np.full(7, L),
                          rng.randint(0, L + 1, size=3000),
                          np.repeat(keys[:live], 5)]).astype(np.int32)
    got = np.asarray(keyed_lookup(jnp.asarray(idx), jnp.asarray(keys),
                                  jnp.asarray(table)))
    want = np.zeros((len(idx), C), np.int32)
    for k in range(live):
        want[idx == keys[k]] = table[k]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(_gather_lookup(jnp.asarray(idx), jnp.asarray(keys),
                                       jnp.asarray(table))))
    assert got.dtype == np.int32
    assert (got[idx == L] == 0).all()            # L is no key: reads zeros
    assert (got[~np.isin(idx, keys[:live])] == 0).all()


# ------------------------------------------------------------ whole trees

def _dense(n=2400, f=10, seed=3, cat=False):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    if cat:
        X[:, 1] = rng.randint(0, 12, size=n)
        X[:, 4] = rng.randint(0, 5, size=n)
    X[rng.rand(n, f) < 0.03] = np.nan            # missing bins route too
    Z = np.nan_to_num(X)
    y = (Z[:, 0] + Z[:, 2] * Z[:, 3] + 0.3 * (Z[:, 1] % 3 == 0)
         + 0.1 * rng.randn(n) > 0.8).astype(np.float32)
    return X, y


def _flags(n=2400, groups=4, per_group=6, seed=4):
    """Dense columns beside mutually exclusive flag groups: EFB bundles."""
    rng = np.random.RandomState(seed)
    Xd = rng.rand(n, 3)
    flags = np.zeros((n, groups * per_group))
    picks = rng.randint(0, per_group, size=(n, groups))
    for g in range(groups):
        flags[np.arange(n), g * per_group + picks[:, g]] = 1.0
    y = (Xd[:, 0] + 0.3 * (picks[:, 0] > per_group // 2)
         + 0.1 * rng.randn(n) > 0.65).astype(np.float32)
    return np.concatenate([Xd, flags], axis=1).astype(np.float32), y


BASE = dict(objective="binary", num_leaves=31, max_bin=63, verbose=-1,
            min_data_in_leaf=5, device="cpu", learning_rate=0.2,
            metric="none", bagging_fraction=0.8, bagging_freq=1)

# name -> (data, parameters, routing table's columns)
CASES = {
    "serial": (_dense, dict(), 8),
    "wave_of_one": (_dense, dict(tpu_hist_slots=1, num_leaves=8), 8),
    "categorical": (lambda: _dense(cat=True),
                    dict(categorical_feature="1,4"), 8),
    "efb_native": (_flags, dict(enable_bundle=True), 13),
    "efb_unpack": (_flags, dict(enable_bundle=True, tpu_efb_unpack=True), 8),
    "no_row_compact": (_dense, dict(tpu_row_compact=False), 6),
    "streamed": (_dense, dict(tpu_residency="stream",
                              tpu_stream_shard_rows=1024), 6),
    "data_parallel": (_dense, dict(tree_learner="data"), 8),
}


def _train(case, rounds=5):
    make, extra, _ = CASES[case]
    X, y = make()
    params = dict(BASE, **extra)
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=rounds, verbose_eval=False,
                    keep_training_booster=True)
    return bst, X


@pytest.mark.parametrize("case", sorted(CASES))
def test_trees_equal_the_gather_routed_trees(case, monkeypatch):
    """The same booster with routing's lookup replaced by a per-row gather
    grows the same trees and leaves every row in the same leaf."""
    bst, X = _train(case)
    assert all(t.num_leaves > 2 for t in bst.trees)
    monkeypatch.setattr(grower, "keyed_lookup", _gather_lookup)
    ref, _ = _train(case)
    assert bst.model_to_string() == ref.model_to_string()
    np.testing.assert_array_equal(bst.predict(X, pred_leaf=True),
                                  ref.predict(X, pred_leaf=True))
    # the training-time routing itself: the scores it accumulated per row
    np.testing.assert_array_equal(np.asarray(bst._gbdt.score),
                                  np.asarray(ref._gbdt.score))


@pytest.mark.parametrize("case", ["serial", "efb_native", "efb_unpack",
                                  "no_row_compact", "streamed"])
def test_route_table_cols_gauge(case):
    """The table's width is published beside the one-hot's
    (``booster.hist_slots``): six columns of the split, five of the bundle
    under native bundle-space routing, the two children's next slots under
    ``row_compact``."""
    from lightgbm_tpu import observability as obs
    bst, _ = _train(case, rounds=1)
    reg = obs.get_registry()
    assert reg.gauge("route.table_cols").value == CASES[case][2]
    assert reg.gauge("booster.hist_slots").value == bst._gbdt.spec.hist_slots
    assert grower.route_table_cols(bst._gbdt.spec, bst._gbdt.bundle) \
        == CASES[case][2]
