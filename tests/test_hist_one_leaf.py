"""The one-leaf form of the chunk matmul (ops/histogram.py:
``one_leaf_form``, ``build_histograms(one_leaf=...)``; the kernel is
ops/pallas_histogram.hist_one_leaf_chunk, interpreted here): a wave whose
pending leaves number ONE builds its histogram from two narrow one-hots of
the split bin code where the general form spends 25 slots' columns on one
slot. Same five bf16 hi/lo channels, 0/1 one-hots, f32 accumulation: against
the general form at ``num_slots=1`` and against a float64 histogram, streamed
and compacted, at bin counts the split does and does not divide, uint8 and
uint16 codes, feature counts the group does and does not divide, with
weight-0 rows; and one grown tree with the form on against the tree with the
form off, node for node.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.ops import histogram, pallas_histogram
from lightgbm_tpu.ops.histogram import (build_histograms, one_leaf_break_even,
                                        one_leaf_form, pack_rows,
                                        packed_row_bytes)

N, CHUNK, LEAVES = 2048, 512, 3


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(pallas_histogram, "_INTERPRET", True)


def _table(F, B, dtype, seed=11):
    """Rows of three leaves, leaf 1 pending in slot 0; a tenth of the rows
    carry weight 0 (padding, out-of-sample)."""
    rng = np.random.RandomState(seed)
    X = rng.randint(0, B, size=(N, F)).astype(dtype)
    inc = (rng.rand(N) < 0.9).astype(np.float32)
    g = rng.randn(N).astype(np.float32) * inc
    h = (np.abs(rng.randn(N)) + 0.5).astype(np.float32) * inc
    leaf_id = rng.randint(0, LEAVES, size=N).astype(np.int32)
    slot_of_leaf = np.array([-1, 0, -1, -1], np.int32)
    return X, g, h, inc, leaf_id, slot_of_leaf


def _float64_histogram(X, g, h, inc, rows, B):
    """The leaf's histogram from the bf16 hi/lo channels' own values, summed
    in float64."""
    w = np.asarray(histogram.weight_channels(
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(inc), False
    ).astype(jnp.float32), np.float64)
    ref = np.zeros((X.shape[1], B, 3))
    for f in range(X.shape[1]):
        codes = X[rows, f].astype(np.int64)
        np.add.at(ref[f, :, 0], codes, w[rows, 0] + w[rows, 1])
        np.add.at(ref[f, :, 1], codes, w[rows, 2] + w[rows, 3])
        np.add.at(ref[f, :, 2], codes, w[rows, 4])
    return ref


def _compact_args(X, g, h, inc, rows, n_active=None):
    n_active = len(rows) if n_active is None else n_active
    row_idx = np.zeros(N, np.int32)
    row_idx[: len(rows)] = rows
    return dict(row_idx=jnp.asarray(row_idx),
                n_active=jnp.asarray(n_active, jnp.int32),
                packed=pack_rows(jnp.asarray(X), g, h, inc, False)[0])


SHAPES = [
    pytest.param(7, 256, np.uint8, id="F7-B256-u8"),       # 7 = 2 x 3 + 1
    pytest.param(6, 64, np.uint8, id="F6-B64-u8"),
    pytest.param(5, 40, np.uint8, id="F5-B40-bundle-pad"),  # 40 / 8 = 5 -> 16
    pytest.param(4, 100, np.uint8, id="F4-B100-undivided"),  # 100 % 8 != 0
    pytest.param(3, 512, np.uint16, id="F3-B512-u16"),      # G = 2
    pytest.param(2, 1024, np.uint16, id="F2-B1024-u16"),    # G = 1
]


@pytest.mark.parametrize("F,B,dtype", SHAPES)
@pytest.mark.parametrize("arm", ["stream", "compact"])
def test_one_leaf_form_sums_the_general_forms_histogram(arm, F, B, dtype):
    X, g, h, inc, leaf_id, slot_of_leaf = _table(F, B, dtype)
    form = one_leaf_form(F, B, CHUNK)
    assert form is not None and form.features_padded >= F
    assert form.bins_hi * form.bins_lo >= B
    rows = np.flatnonzero(leaf_id == 1).astype(np.int32)
    assert len(rows) % CHUNK            # the last chunk is a partial one
    args = (jnp.asarray(X), g, h, inc, jnp.asarray(leaf_id),
            jnp.asarray(slot_of_leaf))
    kw = dict(num_slots=1, num_bins_padded=B, chunk_rows=CHUNK)
    if arm == "compact":
        kw.update(_compact_args(X, g, h, inc, rows))
    general = np.asarray(build_histograms(
        *args, **kw, **(dict(slot_counts=jnp.asarray([len(rows)], jnp.int32))
                        if arm == "compact" else {})))
    one = np.asarray(build_histograms(*args, one_leaf=form, **kw))
    assert one.shape == general.shape == (1, F, B, 3)
    # counts to the unit, sums to the rounding of a different order of f32
    # additions (tests/test_hist_chunk.py's tolerance)
    np.testing.assert_array_equal(one[..., 2], general[..., 2])
    np.testing.assert_allclose(one, general, rtol=0, atol=2e-4)
    ref = _float64_histogram(X, g, h, inc, rows, B)
    np.testing.assert_array_equal(one[0, :, :, 2], ref[..., 2])
    np.testing.assert_allclose(one[0], ref, rtol=0, atol=2e-4)
    assert one[0, :, :, 2].sum() == F * inc[rows].sum() > 0


@pytest.mark.parametrize("slots", [1, 5])
def test_the_leaf_lands_in_slot_zero_and_the_other_slots_stay_empty(slots):
    X, g, h, inc, leaf_id, slot_of_leaf = _table(7, 256, np.uint8)
    out = np.asarray(build_histograms(
        jnp.asarray(X), g, h, inc, jnp.asarray(leaf_id),
        jnp.asarray(slot_of_leaf), num_slots=slots, num_bins_padded=256,
        chunk_rows=CHUNK, one_leaf=one_leaf_form(7, 256, CHUNK)))
    assert out.shape == (slots, 7, 256, 3)
    assert out[0, :, :, 2].sum() > 0 and not np.abs(out[1:]).sum()


@pytest.mark.parametrize("n_active", [0, 1, CHUNK, CHUNK + 3])
def test_a_compacted_pass_reads_the_first_n_active_rows(n_active):
    """``n_active`` at 0 (no chunk runs), inside a chunk, on a chunk's edge
    and past one: the entries of ``row_idx`` beyond it carry weight 0."""
    X, g, h, inc, leaf_id, slot_of_leaf = _table(7, 256, np.uint8)
    rows = np.flatnonzero(leaf_id == 1).astype(np.int32)
    out = np.asarray(build_histograms(
        jnp.asarray(X), g, h, inc, jnp.asarray(leaf_id),
        jnp.asarray(slot_of_leaf), num_slots=2, num_bins_padded=256,
        chunk_rows=CHUNK, one_leaf=one_leaf_form(7, 256, CHUNK),
        **_compact_args(X, g, h, inc, rows, n_active)))
    ref = _float64_histogram(X, g, h, inc, rows[:n_active], 256)
    np.testing.assert_array_equal(out[0, :, :, 2], ref[..., 2])
    np.testing.assert_allclose(out[0], ref, rtol=0, atol=2e-4)


def test_weight_zero_rows_of_the_pending_leaf_add_nothing():
    X, g, h, inc, leaf_id, slot_of_leaf = _table(7, 256, np.uint8)
    keep = (np.arange(N) % 2 == 0).astype(np.float32)
    args = (jnp.asarray(X), g * keep, h * keep, inc * keep,
            jnp.asarray(leaf_id), jnp.asarray(slot_of_leaf))
    out = np.asarray(build_histograms(
        *args, num_slots=1, num_bins_padded=256, chunk_rows=CHUNK,
        one_leaf=one_leaf_form(7, 256, CHUNK)))
    rows = np.flatnonzero((leaf_id == 1) & (keep > 0))
    ref = _float64_histogram(X, g * keep, h * keep, inc * keep, rows, 256)
    np.testing.assert_array_equal(out[0, :, :, 2], ref[..., 2])


@pytest.mark.parametrize("features,bins,chunk,expect", [
    (67, 256, 32768, (8, 32, 3, 24, 24, 2048)),
    (2000, 256, 4096, (8, 32, 3, 672, 32, 2048)),
    (28, 64, 32768, (8, 16, 3, 12, 12, 2048)),
    (10, 512, 1024, (8, 64, 2, 8, 8, 1024)),
    (10, 1024, 512, (8, 128, 1, 12, 12, 512)),
    (10, 2048, 512, None),          # a hi one-hot wider than one MXU tile
    (10, 256, 768, (8, 32, 3, 4, 4, 256)),
    (10, 256, 300, None),           # a chunk no row tile divides
], ids=["criteo", "epsilon", "higgs63", "u16-512", "u16-1024", "too-many-bins",
        "small-tile", "odd-chunk"])
def test_the_shape_rule(features, bins, chunk, expect):
    form = one_leaf_form(features, bins, chunk)
    assert (None if form is None else tuple(form)) == expect
    if form is not None:
        assert form.acc_bytes == form.groups * form.group * form.bins_hi * 512


def test_the_one_leaf_break_even_follows_the_forms_own_cost():
    """The cheaper matmul leaves the gather and the sort to decide: at 67
    columns the one-leaf wave compacts under 0.20 of the rows where the
    general form compacts under 0.70; at 2,000 columns under 0.74 (0.95)."""
    def frac(features, rows, chunk):
        form = one_leaf_form(features, 256, chunk)
        return one_leaf_break_even(
            rows, form, packed_row_bytes(features, "u8", False), 25)
    assert frac(67, 14680064, 32768) == pytest.approx(0.196, abs=0.005)
    assert frac(2000, 401408, 4096) == pytest.approx(0.743, abs=0.01)
    assert frac(8, 100000, 4096) == histogram._MIN_COMPACT_FRAC
    general = histogram.compact_break_even(
        14680064, 67, 256, packed_row_bytes(67, "u8", False), 25)
    assert frac(67, 14680064, 32768) < general


def _grow(form_on: bool, monkeypatch, **extra):
    monkeypatch.setattr(pallas_histogram, "_INTERPRET", form_on)
    obs.reset_for_tests()
    rng = np.random.RandomState(1)
    X = rng.rand(5000, 9).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.1 * rng.randn(5000) > 0.8
         ).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, max_bin=255, verbose=-1,
                  min_data_in_leaf=5, device="cpu", tpu_hist_chunk=1024,
                  **extra)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=2,
                    verbose_eval=False)
    passes = obs.get_registry().summary("grow.one_leaf_passes").values()
    return bst.model_to_string(), bst.predict(X), passes


@pytest.mark.parametrize("extra", [
    {}, dict(tpu_row_compact=False), dict(bagging_fraction=0.5, bagging_freq=1),
    dict(tpu_compact_frac=0.9)],
    ids=["default", "no-row-compact", "bagging", "compact-early"])
def test_a_grown_tree_is_the_same_node_for_node(monkeypatch, extra):
    on, pred_on, passes_on = _grow(True, monkeypatch, **extra)
    off, pred_off, passes_off = _grow(False, monkeypatch, **extra)
    assert passes_on == [2.0, 2.0] and passes_off == []
    assert on == off
    np.testing.assert_array_equal(pred_on, pred_off)


@pytest.mark.parametrize("extra", [
    dict(tpu_hist_f64=True), dict(tpu_hist_kernel="pallas"),
    dict(tpu_residency="stream")], ids=["f64", "pallas", "stream"])
def test_the_other_weight_modes_and_kernels_keep_the_general_form(
        monkeypatch, extra):
    """Statically: no wave of such a program takes the form, and it carries
    no counter."""
    *_, passes = _grow(True, monkeypatch, **extra)
    assert passes == []
    assert obs.get_registry().snapshot()["gauges"]["hist.one_leaf_frac"] == 0
