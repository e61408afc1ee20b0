"""Stream or compact a wave's histogram pass (ops/histogram.py:
``compact_break_even`` / ``resolve_compact_frac``): the threshold as a
static function of the shapes, ``tpu_compact_frac`` = 0 (auto) by default,
an explicit value still forcing an arm. The rule is pure arithmetic on the
v5e's readings (PERF.md, PR 31); what a CPU run can hold it to is its
shape: in (0, 1], rising with the width, the benchmark's wide tables on the
"stream the root only" side and a 10-column table on the other, the Pallas
cap, and that the booster hands the grower exactly what the rule says.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.ops.histogram import (PALLAS_COMPACT_FRAC_CAP,
                                        compact_break_even,
                                        packed_row_bytes,
                                        resolve_compact_frac,
                                        sort_is_one_word)

ROWS, SLOTS = 1 << 20, 25


def _rule(features, bins=256, rows=ROWS, code_mode="u8", exact=False,
          row_bytes=None):
    if row_bytes is None:
        row_bytes = packed_row_bytes(features, code_mode, exact)
    return compact_break_even(rows=rows, features=features, bins_padded=bins,
                              row_bytes=row_bytes, num_slots=SLOTS,
                              exact=exact)


WIDTHS = (1, 2, 4, 10, 28, 67, 137, 256, 700, 2000, 8000)


@pytest.mark.parametrize("bins", [16, 64, 256])
@pytest.mark.parametrize("exact", [False, True], ids=["hilo", "f32"])
def test_rule_rises_with_the_width_inside_the_unit_interval(bins, exact):
    """At one packed row the threshold only rises with the build's width:
    a wasted streamed row is dearer the wider the table."""
    got = [_rule(f, bins, exact=exact, row_bytes=77) for f in WIDTHS]
    assert all(0.0 < v <= 1.0 for v in got)
    assert got == sorted(got) and got[-1] > got[0]
    # and with the row that goes with each width it stays in (0, 1]
    assert all(0.0 < _rule(f, bins, exact=exact) <= 1.0 for f in WIDTHS)


def test_rule_follows_the_gathers_steps_not_a_line():
    """The v5e fetches a packed row of 28 to 59 bytes at three times the
    cost of one of 60 to 128 (PERF.md, PR 31): at one width the threshold
    falls where the row enters that band and rises again beyond it. So the
    rule is NOT monotone in the width when the row grows with it: 18
    columns (28 bytes) compact later than 14 (24 bytes)."""
    at = {b: _rule(28, row_bytes=b) for b in (20, 24, 28, 38, 59, 60, 128)}
    assert at[20] == at[24] > at[28] > at[38] > at[59] < at[60] == at[128]
    assert _rule(18) < _rule(14) and _rule(54) > _rule(38)
    # beyond the table a row costs a little more a byte, never less
    assert _rule(67, row_bytes=2010) < _rule(67, row_bytes=256)


@pytest.mark.parametrize("features,rows,side", [
    (67, 14_680_064, "root-only"),                # criteo67-255
    (2000, 401_408, "root-only"),                 # epsilon-255
    (28, 2_097_152, "narrow"),                    # HIGGS-like, 38-byte rows
    (10, 4_194_304, "narrow"),
    (4, 1 << 20, "narrow")],
    ids=["criteo67", "epsilon", "higgs28", "ten-columns", "four-columns"])
def test_rule_at_the_measured_shapes(features, rows, side):
    """Every wave after the root has under half of the rows pending, so a
    value above 0.5 is "stream the root only"; a narrow table keeps
    streaming its early waves. The narrow values sit inside the window the
    chip's sweeps put within 2% of the best (0.2-0.35 at 28 columns,
    0.24-0.45 at 10)."""
    frac = _rule(features, rows=rows)
    assert (frac > 0.5) == (side == "root-only")
    if features == 28:
        assert 0.2 <= frac <= 0.35
    if features == 10:
        assert 0.24 <= frac <= 0.45


def test_rule_reads_the_sort_and_the_weight_mode():
    assert sort_is_one_word(1 << 24, 127) and not sort_is_one_word(
        (1 << 24) + 1, 25) and not sort_is_one_word(1 << 20, 128)
    # pairs cost three one-word sorts: a compacted pass has to save more
    assert _rule(10, rows=(1 << 24) + 256) < _rule(10, rows=1 << 24)
    # f32 columns at Precision.HIGHEST: a dearer matmul in both arms over
    # the same gather, so compaction pays earlier
    assert _rule(10, exact=True, row_bytes=20) > _rule(10, row_bytes=20)
    # a narrower packed row (two codes a byte) is a cheaper gather
    assert _rule(28, bins=16, code_mode="u4") > _rule(28, bins=16)


@pytest.mark.parametrize("requested,kernel,want", [
    (0.0, "xla", "rule"), (0.6, "xla", 0.6), (1.0, "xla", 1.0),
    (1e-9, "xla", 1e-9),
    (0.0, "pallas", PALLAS_COMPACT_FRAC_CAP),
    (1.0, "mixed", PALLAS_COMPACT_FRAC_CAP), (0.1, "mixed", 0.1)],
    ids=["auto", "explicit", "one", "never", "auto-pallas", "one-mixed",
         "under-the-cap"])
def test_resolve_auto_explicit_and_the_pallas_cap(requested, kernel, want):
    shape = dict(rows=ROWS, features=67, bins_padded=256, row_bytes=77,
                 num_slots=SLOTS, exact=False)
    got = resolve_compact_frac(requested, kernel, **shape)
    assert got == (compact_break_even(**shape) if want == "rule" else want)


# ------------------------------------------------ what the booster resolves

def _booster(params, n=1500, f=12, seed=4):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  metric="none", **params)
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


@pytest.fixture
def clean_registry():
    obs.reset_for_tests()
    yield obs
    obs.reset_for_tests()


@pytest.mark.parametrize("params,code_bytes", [
    (dict(max_bin=255), 12),
    (dict(max_bin=15), 6),                          # u4: two codes a byte
    (dict(max_bin=63), 9),                          # u6: four codes, 3 bytes
    (dict(max_bin=255, tpu_hist_f64=True), 12)],
    ids=["u8", "u4", "u6", "f64"])
def test_booster_resolves_auto_from_its_own_shapes(clean_registry, params,
                                                   code_bytes):
    g = _booster(params)._gbdt
    spec = g.spec
    f64 = bool(params.get("tpu_hist_f64"))
    assert spec.hist_f64 == f64
    channels = 3 if f64 else 5
    want = compact_break_even(
        rows=g.num_data_padded, features=spec.num_features,
        bins_padded=spec.num_bins_padded,
        row_bytes=code_bytes + channels * (4 if f64 else 2),
        num_slots=spec.hist_slots, exact=f64)
    assert spec.compact_frac == want and 0.0 < want <= 1.0
    # published where hist_pass_shape's readings are
    assert obs.snapshot()["gauges"]["hist.compact_frac"] == want


def test_booster_keeps_an_explicit_value_and_says_which(tmp_path):
    obs.reset_for_tests()
    obs.configure(telemetry_dir=str(tmp_path))       # events are recorded
    try:
        auto = _booster({})._gbdt.spec.compact_frac
        fixed = _booster(dict(tpu_compact_frac=0.4))._gbdt.spec.compact_frac
        assert fixed == 0.4 != auto
        got = [(e["args"]["compact_rule"], e["args"]["compact_frac"])
               for e in obs.get_tracer().events()
               if e.get("name") == "hist_pass_shape"]
        assert got == [("auto", auto), ("explicit", 0.4)]
    finally:
        obs.reset_for_tests()


def test_bundled_table_is_sized_in_bundle_space(clean_registry):
    """Under EFB the build's width is the bundle columns and the bundle's
    bin axis (``hist_bins``), not the raw columns."""
    rng = np.random.RandomState(7)
    n, f = 2000, 40
    X = np.zeros((n, f), np.float32)
    hot = rng.randint(0, f, size=n)                 # one-hot: one bundle
    X[np.arange(n), hot] = rng.rand(n) + 0.5
    y = (hot % 2).astype(np.float32)
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  metric="none", enable_bundle=True, max_bin=15)
    g = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=y, params=params))._gbdt
    assert g.bundle is not None and g.spec.hist_bins > 0
    cols = g.Xb.shape[1]
    assert cols < f
    want = compact_break_even(
        rows=g.num_data_padded, features=cols, bins_padded=g.spec.hist_bins,
        row_bytes=packed_row_bytes(cols, g.spec.code_mode, False),
        num_slots=g.spec.hist_slots)
    assert g.spec.compact_frac == want
