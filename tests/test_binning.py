"""BinMapper tests (reference semantics: src/io/bin.cpp FindBin/ValueToBin)."""
import numpy as np
import pytest

from lightgbm_tpu.binning import (BIN_CATEGORICAL, MISSING_NAN, MISSING_NONE,
                                  MISSING_ZERO, BinMapper, greedy_find_bin,
                                  sample_for_binning)


def _mk(values, total=None, max_bin=255, bin_type="numerical",
        use_missing=True, zero_as_missing=False, min_data_in_bin=3):
    values = np.asarray(values, dtype=np.float64)
    total = total if total is not None else len(values)
    m = BinMapper()
    m.find_bin(values, total, max_bin, min_data_in_bin, 0, bin_type,
               use_missing, zero_as_missing)
    return m


def test_simple_numerical():
    vals = np.repeat(np.arange(1.0, 11.0), 10)
    m = _mk(vals)
    assert m.missing_type == MISSING_NONE
    assert not m.is_trivial
    # every distinct value should round-trip to a distinct bin
    bins = m.value_to_bin(np.arange(1.0, 11.0))
    assert len(np.unique(bins)) == 10
    # ordering preserved
    assert (np.diff(bins) > 0).all()


def test_monotonic_mapping():
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(5000)
    m = _mk(vals, max_bin=63)
    assert m.num_bin <= 63
    q = np.sort(rng.standard_normal(1000))
    bins = m.value_to_bin(q)
    assert (np.diff(bins) >= 0).all()


def test_equal_count_binning():
    rng = np.random.default_rng(1)
    vals = rng.standard_normal(20000)
    m = _mk(vals, max_bin=32, min_data_in_bin=3)
    bins = m.value_to_bin(vals)
    counts = np.bincount(bins, minlength=m.num_bin)
    # greedy equal-count: no bin (except zero's) should be wildly off-balance
    nonzero_counts = counts[counts > 0]
    assert nonzero_counts.max() < 8 * nonzero_counts.min() + 100


def test_nan_missing_type():
    vals = np.array([1.0, 2.0, 3.0, np.nan, 4.0, np.nan] * 10)
    m = _mk(vals)
    assert m.missing_type == MISSING_NAN
    # NaN maps to the last bin (bin.h:452-455)
    assert m.value_to_bin(np.array([np.nan]))[0] == m.num_bin - 1
    # non-NaN values stay out of the NaN bin
    assert (m.value_to_bin(np.array([1.0, 2.0, 4.0])) < m.num_bin - 1).all()


def test_no_use_missing():
    vals = np.array([1.0, 2.0, 3.0, np.nan, 4.0] * 10)
    m = _mk(vals, use_missing=False)
    assert m.missing_type == MISSING_NONE
    # NaN treated as zero (bin.h:453-458)
    zero_bin = m.value_to_bin(np.array([0.0]))[0]
    assert m.value_to_bin(np.array([np.nan]))[0] == zero_bin


def test_zero_as_missing():
    vals = np.concatenate([np.arange(1, 50, dtype=np.float64),
                           -np.arange(1, 50, dtype=np.float64)])
    m = _mk(vals, total=200, zero_as_missing=True)  # 102 implicit zeros
    assert m.missing_type == MISSING_ZERO
    assert m.default_bin == m.value_to_bin(np.array([0.0]))[0]


def test_zero_gets_own_bin():
    # FindBinWithZeroAsOneBin: zero separated from +/- ranges (bin.cpp:146-204)
    vals = np.concatenate([np.linspace(-5, -1, 40), np.linspace(1, 5, 40)])
    m = _mk(vals, total=120)  # 40 implicit zeros
    zb = m.value_to_bin(np.array([0.0]))[0]
    assert m.value_to_bin(np.array([-1.0]))[0] < zb < m.value_to_bin(np.array([1.0]))[0]


def test_categorical():
    rng = np.random.default_rng(2)
    vals = rng.choice([1, 2, 3, 5, 8], size=1000,
                      p=[0.5, 0.2, 0.15, 0.1, 0.05]).astype(np.float64)
    m = _mk(vals, bin_type=BIN_CATEGORICAL)
    assert m.bin_type == BIN_CATEGORICAL
    # most frequent category gets bin... bins ordered by count desc
    b1 = m.value_to_bin(np.array([1.0]))[0]
    b2 = m.value_to_bin(np.array([2.0]))[0]
    assert b1 < b2 or b1 == 1  # cat 0 swap rule only when category==0 present
    # unseen category -> last bin
    assert m.value_to_bin(np.array([77.0]))[0] == m.num_bin - 1
    # category 0 never in bin 0 (bin.cpp:313-321 CHECK(default_bin > 0))
    vals0 = rng.choice([0, 1, 2], size=300, p=[0.6, 0.3, 0.1]).astype(np.float64)
    m0 = _mk(vals0, bin_type=BIN_CATEGORICAL)
    assert m0.value_to_bin(np.array([0.0]))[0] > 0


def test_trivial_feature():
    m = _mk(np.ones(100) * 5.0, total=100)
    # single distinct value -> one bin -> trivial
    assert m.is_trivial or m.num_bin <= 2


def test_greedy_find_bin_small():
    vals = np.array([1.0, 2.0, 3.0])
    counts = np.array([10, 10, 10])
    ub = greedy_find_bin(vals, counts, 255, 30, 3)
    assert ub[-1] == np.inf
    assert len(ub) == 3
    assert ub[0] == pytest.approx(1.5)
    assert ub[1] == pytest.approx(2.5)


def test_sampling():
    rng = np.random.default_rng(3)
    data = rng.standard_normal((1000, 3))
    data[:, 1] = 0.0
    idx, per_feature = sample_for_binning(data, 100, 1)
    assert len(idx) == 100
    assert len(per_feature) == 3
    assert len(per_feature[1]) == 0  # all-zero column filtered


def test_value_to_bin_boundary_semantics():
    # value <= upper_bound goes to that bin (bin.h:466-471)
    m = BinMapper()
    m.num_bin = 4
    m.bin_upper_bound = np.array([1.0, 2.0, 3.0, np.inf])
    m.missing_type = MISSING_NONE
    m.is_trivial = False
    bins = m.value_to_bin(np.array([0.5, 1.0, 1.5, 2.0, 2.5, 100.0]))
    assert list(bins) == [0, 0, 1, 1, 2, 3]


def test_collect_distinct_interior_zero_splice_unguarded():
    """A fully-dense column crossing negative->positive still gets a
    (0.0, 0) distinct entry: the reference's interior splice
    (bin.cpp:245-248) is unguarded, unlike the all-positive/all-negative
    edge splices which only fire when zeros exist."""
    from lightgbm_tpu.binning import BinMapper

    vals = np.array([-2.0, -1.0, 1.0, 2.0], dtype=np.float64)
    uniq, cnts = BinMapper._collect_distinct(vals, zero_cnt=0)
    zi = np.searchsorted(uniq, 0.0)
    assert uniq[zi] == 0.0 and cnts[zi] == 0
    # edge splices stay guarded: all-positive with no zeros -> no 0 entry
    uniq2, _ = BinMapper._collect_distinct(
        np.array([1.0, 2.0], dtype=np.float64), zero_cnt=0)
    assert 0.0 not in uniq2
    # and with zeros they fire
    uniq3, cnts3 = BinMapper._collect_distinct(
        np.array([1.0, 2.0], dtype=np.float64), zero_cnt=5)
    assert uniq3[0] == 0.0 and cnts3[0] == 5
