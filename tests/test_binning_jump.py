"""``binning.greedy_find_bin`` jumps from bin close to bin close on prefix
sums; the reference's one-step-per-distinct-value walk (bin.cpp:71-144), kept
here as the oracle, has to give the same bounds to the bit, and whole
mappers have to equal those of the walk column for column."""
import numpy as np
import pytest

from lightgbm_tpu import binning
from lightgbm_tpu.binning import BinMapper, sample_for_binning


def greedy_find_bin_walk(distinct_values, counts, max_bin, total_cnt,
                         min_data_in_bin):
    """The loop as it stood before the jump (PR 28's binning.py, verbatim)."""
    num_distinct = len(distinct_values)
    bin_upper_bound = []
    if num_distinct <= max_bin:
        cur_cnt_inbin = 0
        for i in range(num_distinct - 1):
            cur_cnt_inbin += int(counts[i])
            if cur_cnt_inbin >= min_data_in_bin:
                bin_upper_bound.append((float(distinct_values[i]) + float(distinct_values[i + 1])) / 2.0)
                cur_cnt_inbin = 0
        bin_upper_bound.append(np.inf)
        return bin_upper_bound
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, int(total_cnt // min_data_in_bin)))
    mean_bin_size = total_cnt / max_bin
    is_big = counts >= mean_bin_size
    rest_bin_cnt = max_bin - int(is_big.sum())
    rest_sample_cnt = int(total_cnt - counts[is_big].sum())
    mean_bin_size = rest_sample_cnt / rest_bin_cnt if rest_bin_cnt > 0 else np.inf
    upper_bounds = []
    lower_bounds = [float(distinct_values[0])]
    cur_cnt_inbin = 0
    for i in range(num_distinct - 1):
        if not is_big[i]:
            rest_sample_cnt -= int(counts[i])
        cur_cnt_inbin += int(counts[i])
        if (is_big[i] or cur_cnt_inbin >= mean_bin_size
                or (is_big[i + 1] and cur_cnt_inbin >= max(1.0, mean_bin_size * 0.5))):
            upper_bounds.append(float(distinct_values[i]))
            lower_bounds.append(float(distinct_values[i + 1]))
            if len(upper_bounds) >= max_bin - 1:
                break
            cur_cnt_inbin = 0
            if not is_big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = rest_sample_cnt / rest_bin_cnt if rest_bin_cnt > 0 else np.inf
    bin_cnt = len(upper_bounds) + 1
    out = [(upper_bounds[i] + lower_bounds[i + 1]) / 2.0 for i in range(bin_cnt - 1)]
    out.append(np.inf)
    return out


def _columns(rng, n):
    """Columns that reach every branch of the walk: continuous, a few heavy
    values among many light ones, runs of heavy values, zero-inflated counts,
    both signs, NaNs, and nearly constant."""
    heavy = np.where(rng.random(n) < 0.5, rng.integers(0, 4, n), rng.random(n) * 50)
    return {
        "continuous": rng.beta(4.0, 4.0, n),
        "float32 continuous": rng.normal(size=n).astype(np.float32).astype(np.float64),
        "counts": np.floor(rng.lognormal(1.5, 1.6, n)),
        "zero inflated": np.where(rng.random(n) < 0.4, 0.0, np.floor(rng.pareto(1.3, n) * 20)),
        "heavy among light": heavy,
        "heavy runs": np.where(rng.random(n) < 0.7, rng.integers(-3, 3, n) * 1.0, rng.normal(size=n) * 10),
        "both signs": rng.normal(size=n) * 100,
        "with nan": np.where(rng.random(n) < 0.05, np.nan, rng.gamma(2.0, 2.0, n)),
        "nearly constant": np.where(rng.random(n) < 0.999, 7.0, rng.random(n)),
        "small integers": rng.integers(0, 300, n) * 1.0,
    }


@pytest.mark.parametrize("max_bin,min_data_in_bin", [(255, 3), (63, 3), (15, 1), (255, 50), (4, 0)])
def test_jump_gives_the_walks_bounds(max_bin, min_data_in_bin):
    rng = np.random.default_rng(max_bin * 1000 + min_data_in_bin)
    for n in (3000, 20000):
        for name, col in _columns(rng, n).items():
            values = col[~np.isnan(col)]
            distinct, counts = np.unique(values, return_counts=True)
            got = binning.greedy_find_bin(distinct, counts.astype(np.int64), max_bin,
                                          len(values), min_data_in_bin)
            want = greedy_find_bin_walk(distinct, counts.astype(np.int64), max_bin,
                                        len(values), min_data_in_bin)
            assert got == want, (name, n)


def test_mappers_equal_the_walks_column_for_column(monkeypatch):
    """Whole mappers through ``sample_for_binning`` + ``find_bin``: the
    blocked column copy and the jump against a strided copy and the walk."""
    rng = np.random.default_rng(7)
    n = 6000
    cols = _columns(rng, n)
    X = np.stack(list(cols.values()), axis=1)
    _, per_feature = sample_for_binning(X, 4000, seed=3)

    def mappers():
        out = []
        for j in range(X.shape[1]):
            m = BinMapper()
            m.find_bin(per_feature[j], 4000, 255, 3, 2)
            out.append(m)
        return out

    new = mappers()
    monkeypatch.setattr(binning, "greedy_find_bin", greedy_find_bin_walk)
    idx = np.sort(np.random.default_rng(3).choice(n, size=4000, replace=False))
    for j, (a, b) in enumerate(zip(new, mappers())):
        strided = X[idx][:, j]
        keep = (np.abs(strided) > binning.K_EPSILON) | np.isnan(strided)
        np.testing.assert_array_equal(per_feature[j], strided[keep])
        assert a.num_bin == b.num_bin and a.missing_type == b.missing_type
        assert a.is_trivial == b.is_trivial and a.default_bin == b.default_bin
        assert a.sparse_rate == b.sparse_rate
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)
