"""The wave's tail over the slots the wave holds (PR 35).

``_apply_wave_splits`` runs the cache's subtract-and-write-back and the split
scan over the occupied prefix of the wave's slots, ``b`` slot pairs a trip of
one loop, where the table is wide; a narrow table keeps the static tail (all
``hist_slots`` pairs at once, no loop), which is the parent's program to the
lowered text. ``b`` is ``grower.scan_block_pairs`` of the shapes; the tests
move its one constant to put small tables on either side of it.

- the rule as a pure function, at the three cells' shapes and at 28 columns;
- whole trees: the blocked tail against the static one, to the bit, for every
  consumer of the tail (serial, ``data`` / ``feature`` / ``voting`` on four
  host devices, EFB native and unpacked, a categorical column, NaNs,
  streamed residency, ``tree_batch=4``, exact leaf-wise waves), with a block
  size that divides ``hist_slots`` and one that does not;
- the counter: ``grow.scan_slots`` is the loop's own count, and the gauge
  ``scan.block_slots`` publishes ``b``.
"""
import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import grower
from lightgbm_tpu import observability as obs
from lightgbm_tpu.grower import WaveStats, scan_block_pairs, wave_totals

STATIC = 1 << 40              # no table is this wide: the static tail


# ----------------------------------------------------------------- the rule

@pytest.mark.parametrize("cell,slots,features,bins,want", [
    # epsilon-255-train: [2b, 2000, 256, 3] f32 is 12.3 MB a pair
    ("epsilon-255-train", 25, 2000, 256, 4),
    # criteo67-255-train: all 50 slots are 10.3 MB
    ("criteo67-255-train", 25, 67, 256, 25),
    # a shard of criteo67-255-dp4-train scans its 17-column block
    ("criteo67-255-dp4-train", 25, 17, 256, 25),
    ("higgs-28-columns", 25, 28, 256, 25),
    ("one-slot", 1, 2000, 256, 1),
])
def test_block_rule_at_the_cells_shapes(cell, slots, features, bins, want):
    b = scan_block_pairs(slots, features, bins)
    assert b == want, cell
    # a block is at least the constant's bytes, or the whole wave
    if b < slots:
        assert 2 * b * features * bins * 12 >= grower._SCAN_BLOCK_BYTES


def test_block_rule_makes_equal_blocks(monkeypatch):
    """Blocks of equal size: the smallest b that covers ``hist_slots`` in
    as many trips as the byte target allows."""
    pair = 2 * 8 * 64 * 12
    for target_pairs, slots, want in [(1, 25, 1), (3, 25, 3), (4, 25, 4),
                                      (6, 25, 5), (8, 25, 7), (12, 25, 9),
                                      (13, 25, 13), (24, 25, 13), (25, 25, 25),
                                      (3, 7, 3), (4, 7, 4), (100, 7, 7)]:
        monkeypatch.setattr(grower, "_SCAN_BLOCK_BYTES", target_pairs * pair)
        assert scan_block_pairs(slots, 8, 64) == want, (target_pairs, slots)


# -------------------------------------------------------------- whole trees

def _dense(n=2400, f=12, seed=3, cat=False):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    if cat:
        X[:, 1] = rng.randint(0, 12, size=n)
    X[rng.rand(n, f) < 0.03] = np.nan            # missing bins are scanned too
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
            metric="none", tpu_hist_slots=7)

CASES = {
    "serial": (_dense, dict()),
    "data": (_dense, dict(tree_learner="data", num_machines=4)),
    "feature": (_dense, dict(tree_learner="feature", num_machines=4)),
    "voting": (_dense, dict(tree_learner="voting", num_machines=4, top_k=3)),
    "efb_native": (_flags, dict(enable_bundle=True)),
    "efb_unpack": (_flags, dict(enable_bundle=True, tpu_efb_unpack=True)),
    "voting_efb": (_flags, dict(enable_bundle=True, tree_learner="voting",
                                num_machines=4, top_k=3)),
    "categorical": (lambda: _dense(cat=True), dict(categorical_feature="1")),
    "streamed": (_dense, dict(tpu_residency="stream",
                              tpu_stream_shard_rows=1024)),
    "tree_batch": (_dense, dict(tree_batch=4)),
    "exact_leafwise": (_dense, dict(tpu_wave_size=1, num_leaves=12)),
}


def _train(case, block_bytes, monkeypatch, rounds=4):
    make, extra = CASES[case]
    X, y = make()
    params = dict(BASE, **extra)
    monkeypatch.setattr(grower, "_SCAN_BLOCK_BYTES", block_bytes)
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=rounds, verbose_eval=False,
                    keep_training_booster=True)
    return bst, X


def _pair_bytes(bst) -> int:
    g = bst._gbdt
    f, b = grower.scan_hist_shape(g.spec, g.comm, _hist_cols(g),
                                  g.bundle is not None)
    return 2 * f * b * 12


def _hist_cols(g) -> int:
    cols = g.Xb.shape[1] if g.Xb is not None else g._streamed_grower.num_cols
    return cols // (g.pctx.num_devices if g.pctx.strategy == "feature" else 1)


@pytest.mark.parametrize("case", sorted(CASES))
def test_blocked_tail_grows_the_static_tails_trees(case, monkeypatch):
    """Blocks of three pairs (7 slots: the last block starts early and serves
    two slots again as slots of no leaf) and of one pair, against the static
    tail: the same model text, the same scores, row for row."""
    ref, X = _train(case, STATIC, monkeypatch)
    assert all(t.num_leaves > 4 for t in ref.trees)
    reg = obs.get_registry()
    assert reg.gauge("scan.block_slots").value == ref._gbdt.spec.hist_slots
    pair = _pair_bytes(ref)
    for pairs in (3, 1):
        bst, _ = _train(case, pairs * pair, monkeypatch)
        assert reg.gauge("scan.block_slots").value == pairs
        assert bst.model_to_string() == ref.model_to_string(), pairs
        np.testing.assert_array_equal(np.asarray(bst._gbdt.score),
                                      np.asarray(ref._gbdt.score))


# -------------------------------------------------------------- the counter

def _pending_by_wave(stats) -> list:
    """Pending leaves of each wave, from the loop's own record: a wave's scan
    holds its pending leaves and their siblings; the root has none."""
    w = int(stats.waves)
    held = np.asarray(stats.scan_pending)[:w]
    return [1] + [int(h) // 2 for h in held[1:]]


@pytest.mark.parametrize("pairs", [1, 2, 3, 5])
def test_scan_slots_is_the_loops_own_count(pairs, monkeypatch):
    obs.reset_for_tests()
    bst, _ = _train("serial", STATIC, monkeypatch, rounds=1)
    pair = _pair_bytes(bst)
    obs.reset_for_tests()
    bst, _ = _train("serial", pairs * pair, monkeypatch, rounds=3)
    g = bst._gbdt
    S = g.spec.hist_slots
    b = scan_block_pairs(S, *grower.scan_hist_shape(
        g.spec, g.comm, _hist_cols(g), False))
    assert b == {1: 1, 2: 2, 3: 3, 5: 4}[pairs]          # equal blocks of 7
    records = jax.device_get(g._grow_records)
    bst._ensure_finalized()
    reg = obs.get_registry()
    covered = reg.summary("grow.scan_slots").values()
    held = reg.summary("grow.scan_slots_pending").values()
    assert len(covered) == 3
    for rec, c, h in zip(records, covered, held):
        st = jax.tree.map(lambda a: a[0, 0], rec.stats)
        w = int(st.waves)
        k = _pending_by_wave(st)
        assert max(k) == S and min(k) == 1               # full and thin waves
        per_wave = [2 * b * -(-kw // b) for kw in k]
        assert np.asarray(st.scan_slots)[:w].tolist() == per_wave
        assert c == sum(per_wave)
        assert h <= c <= w * 2 * b * -(-S // b)
        assert c < w * 2 * S                             # thin waves ran short
    obs.reset_for_tests()


def test_static_tail_counts_every_slot_every_wave(monkeypatch):
    """The static tail carries no counter (its program is the parent's): the
    host counts ``2 x hist_slots`` a wave, what that tail covers."""
    obs.reset_for_tests()
    bst, _ = _train("serial", STATIC, monkeypatch, rounds=2)
    g = bst._gbdt
    records = jax.device_get(g._grow_records)
    assert all(rec.stats.scan_slots is None for rec in records)
    bst._ensure_finalized()
    reg = obs.get_registry()
    waves = reg.summary("grow.waves").values()
    assert reg.summary("grow.scan_slots").values() == \
        [w * 2 * g.spec.hist_slots for w in waves]
    obs.reset_for_tests()


def test_wave_totals_sums_the_loops_count_over_the_pace_setting_shard():
    st = WaveStats(waves=np.array([3, 3]),
                   rows_active=np.array([[100, 10, 5], [100, 30, 5]]),
                   compacted=np.array([[False, True, True]] * 2),
                   rows_split=np.array([[100, 40, 9], [100, 70, 9]]),
                   scan_pending=np.array([[1, 2, 8], [1, 2, 8]]),
                   scan_slots=np.array([[4, 4, 8], [4, 4, 8]]))
    t = wave_totals(st, rows_per_device=100, chunk_rows=20, hist_slots=4)
    assert (t["scan_slots"], t["scan_slots_pending"]) == (16, 11)
    t = wave_totals(st._replace(scan_slots=None), rows_per_device=100,
                    chunk_rows=20, hist_slots=4)
    assert (t["scan_slots"], t["scan_slots_pending"]) == (24, 11)
