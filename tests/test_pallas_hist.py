"""Pallas histogram kernel vs the XLA one-hot matmul — the analog of the
reference's GPU_DEBUG_COMPARE cross-check (gpu_tree_learner.cpp:1018-1043),
run in Pallas interpret mode on the CPU test backend."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.ops.histogram import build_histograms, compact_rows


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setattr(ph, "_INTERPRET", True)


def _data(n=4096, f=6, bins=32, leaves=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randint(0, bins, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    inc = (rng.rand(n) > 0.2).astype(np.float32)
    leaf_id = rng.randint(0, leaves, size=n).astype(np.int32)
    return (jnp.asarray(X), jnp.asarray(g), jnp.asarray(h), jnp.asarray(inc),
            jnp.asarray(leaf_id))


def test_pallas_matches_xla_full_pass():
    X, g, h, inc, leaf_id = _data()
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[jnp.arange(4)].set(
        jnp.arange(4))
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=S, num_bins_padded=B,
                                     chunk_rows=1024)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    # count channel must be exact
    np.testing.assert_array_equal(np.asarray(out[..., 2]),
                                  np.asarray(ref[..., 2]))


def test_pallas_matches_xla_compacted():
    X, g, h, inc, leaf_id = _data(seed=2)
    S, B = 4, 32
    # only leaves 1 and 3 pending -> ~1/4 of rows active
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[1].set(0).at[3].set(1)
    row_idx, n_active = compact_rows(leaf_id, slot_of_leaf)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024,
                           row_idx=row_idx, n_active=n_active)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=S, num_bins_padded=B,
                                     chunk_rows=1024, row_idx=row_idx,
                                     n_active=n_active)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out[..., 2]),
                                  np.asarray(ref[..., 2]))


def test_slot_grouped_position_slots_match():
    """slot_counts path: rows pre-sorted by slot, slots derived from position
    — must equal the per-row slot-gather path in BOTH kernels."""
    X, g, h, inc, leaf_id = _data(seed=7)
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[1].set(0).at[3].set(1).at[5].set(2)
    slot_row = slot_of_leaf[leaf_id]
    n_active = jnp.sum((slot_row >= 0).astype(jnp.int32))
    key = jnp.where(slot_row >= 0, slot_row, jnp.int32(2 ** 30))
    row_idx = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum((slot_row[:, None] == jnp.arange(S)[None, :])
                     .astype(jnp.int32), axis=0)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024,
                           row_idx=row_idx, n_active=n_active)
    grouped = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf,
                               num_slots=S, num_bins_padded=B,
                               chunk_rows=1024, row_idx=row_idx,
                               n_active=n_active, slot_counts=counts)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    grouped_pl = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=1024, row_idx=row_idx, n_active=n_active,
        slot_counts=counts)
    np.testing.assert_allclose(np.asarray(grouped_pl), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.slow
def test_train_with_pallas_kernel_matches_xla():
    """End-to-end: tpu_hist_kernel=pallas grows the same trees as xla."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.rand(800, 5)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(float)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 7,
            "min_data_in_leaf": 10, "max_bin": 31}
    m_xla = lgb.train(base, lgb.Dataset(X, label=y), num_boost_round=3)
    m_pl = lgb.train({**base, "tpu_hist_kernel": "pallas"},
                     lgb.Dataset(X, label=y), num_boost_round=3)
    p_x = m_xla.predict(X)
    p_p = m_pl.predict(X)
    np.testing.assert_allclose(p_p, p_x, rtol=1e-4, atol=1e-5)
    # mixed dispatch (xla full passes + pallas compacted passes) likewise
    m_mx = lgb.train({**base, "tpu_hist_kernel": "mixed"},
                     lgb.Dataset(X, label=y), num_boost_round=3)
    np.testing.assert_allclose(m_mx.predict(X), p_x, rtol=1e-4, atol=1e-5)


def test_fast_channels_close_to_hilo():
    """tpu_hist_hilo=false (3 bf16 channels) stays close to the hi/lo sums —
    the GPU reference's accepted-precision-tradeoff mode."""
    X, g, h, inc, leaf_id = _data(seed=5)
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[jnp.arange(4)].set(
        jnp.arange(4))
    full = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                            num_bins_padded=B, chunk_rows=1024)
    fast = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                            num_bins_padded=B, chunk_rows=1024, hilo=False)
    # counts exact; g/h within bf16 rounding of the summands
    np.testing.assert_array_equal(np.asarray(fast[..., 2]),
                                  np.asarray(full[..., 2]))
    denom = np.abs(np.asarray(full[..., :2])) + 1.0
    rel = np.abs(np.asarray(fast[..., :2]) - np.asarray(full[..., :2])) / denom
    assert rel.max() < 0.05, rel.max()
    fast_pl = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=1024, hilo=False)
    np.testing.assert_allclose(np.asarray(fast_pl), np.asarray(fast),
                               rtol=1e-5, atol=1e-4)


def test_pallas_f32_precision_vs_f64():
    """hi/lo bf16 channels keep ~f32 accuracy on large sums."""
    X, g, h, inc, leaf_id = _data(n=8192, f=2, bins=8, leaves=1, seed=3)
    slot_of_leaf = jnp.zeros(2, jnp.int32)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=1, num_bins_padded=8,
                                     chunk_rows=2048)
    Xn, gn, hn = np.asarray(X), np.asarray(g, np.float64), np.asarray(h, np.float64)
    incn = np.asarray(inc, np.float64)
    for f in range(2):
        for b in range(8):
            m = Xn[:, f] == b
            # grad/hess channels sum ALL rows in the bin (callers pre-mask
            # them for bagging); the count channel applies `included`
            assert abs(float(out[0, f, b, 0]) - gn[m].sum()) < 5e-3
            assert abs(float(out[0, f, b, 1]) - hn[m].sum()) < 5e-3
            assert float(out[0, f, b, 2]) == (m & (incn > 0)).sum()


def test_uint16_codes_pack_roundtrip():
    """max_bin > 255 stores uint16 codes (2 little-endian bytes per code in
    the packed u8 rows) — both kernels and the pack/unpack helpers must
    agree with the uint8 semantics."""
    from lightgbm_tpu.ops.histogram import (code_bytes, pack_rows,
                                            unpack_codes)
    rng = np.random.RandomState(11)
    n, f, bins = 2048, 5, 500
    X = jnp.asarray(rng.randint(0, bins, size=(n, f)).astype(np.uint16))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32))
    inc = jnp.ones(n, jnp.float32)
    assert code_bytes(X.dtype) == 2
    packed, ncb = pack_rows(X, g, h, inc, hilo=True)
    codes = unpack_codes(packed[:, :ncb], f, "u16")
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(X, np.int32))

    leaf_id = jnp.asarray(rng.randint(0, 4, size=n).astype(np.int32))
    slot_of_leaf = jnp.full(5, -1, jnp.int32).at[1].set(0).at[3].set(1)
    B = 512
    row_idx, n_active = compact_rows(leaf_id, slot_of_leaf)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=2,
                           num_bins_padded=B, chunk_rows=512)
    cmp = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=2,
                           num_bins_padded=B, chunk_rows=512,
                           row_idx=row_idx, n_active=n_active)
    np.testing.assert_allclose(np.asarray(cmp), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=2, num_bins_padded=B,
                                     chunk_rows=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_uint16_end_to_end_train():
    """max_bin=400 trains through the uint16 dataset path."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.rand(3000, 4)
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 6) + 0.05 * rng.randn(3000)
    m = lgb.train({"objective": "regression", "verbose": -1, "max_bin": 400,
                   "num_leaves": 15, "min_data_in_leaf": 10},
                  lgb.Dataset(X, label=y), num_boost_round=10)
    pred = m.predict(X)
    assert np.mean((pred - y) ** 2) < np.var(y) * 0.3


def test_max_rows_capped_buffers_match():
    """max_rows (static active-row cap) must not change results when
    n_active fits under it."""
    X, g, h, inc, leaf_id = _data(seed=9)
    S, B = 4, 32
    # one small leaf pending -> well under n/4 active
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[2].set(0)
    row_idx, n_active = compact_rows(leaf_id, slot_of_leaf)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=512,
                           row_idx=row_idx, n_active=n_active)
    capped = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=512, row_idx=row_idx, n_active=n_active,
        max_rows=X.shape[0] // 4)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(capped[..., 2]),
                                  np.asarray(ref[..., 2]))


def test_slot_starts_permutation_matches_prefix_layout():
    """Leaf-contiguous permutation + slot_starts (the grower's incremental
    partition layout) must produce the same histograms as the legacy
    slot-grouped prefix, through BOTH kernels."""
    X, g, h, inc, leaf_id = _data(seed=5)
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[jnp.arange(1, 5)].set(
        jnp.arange(4))
    # legacy: stable argsort prefix + per-slot counts
    sr = slot_of_leaf[leaf_id]
    key = jnp.where(sr >= 0, sr, jnp.int32(2 ** 30))
    row_idx = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum((sr[:, None] == jnp.arange(S)[None, :]).astype(
        jnp.int32), axis=0)
    n_active = jnp.sum((sr >= 0).astype(jnp.int32))
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024,
                           row_idx=row_idx, n_active=n_active,
                           slot_counts=counts)
    # incremental layout: rows grouped by leaf id (a valid leaf-contiguous
    # permutation); pending leaves 1..4 serve slots 0..3
    perm = jnp.argsort(leaf_id, stable=True).astype(jnp.int32)
    cnts_leaf = np.bincount(np.asarray(leaf_id), minlength=9)
    starts_leaf = np.zeros(9, np.int64)
    starts_leaf[1:] = np.cumsum(cnts_leaf)[:-1]
    slot_starts = jnp.asarray(starts_leaf[1:5].astype(np.int32))
    slot_counts = jnp.asarray(cnts_leaf[1:5].astype(np.int32))
    out_xla = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf,
                               num_slots=S, num_bins_padded=B,
                               chunk_rows=1024, row_idx=perm,
                               n_active=n_active, slot_counts=slot_counts,
                               slot_starts=slot_starts)
    np.testing.assert_array_equal(np.asarray(out_xla), np.asarray(ref))
    out_pl = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=1024, row_idx=perm, n_active=n_active,
        slot_counts=slot_counts, slot_starts=slot_starts,
        max_rows=X.shape[0])
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out_pl[..., 2]),
                                  np.asarray(ref[..., 2]))


def test_kernel_choice_is_a_function_of_config_only():
    """tpu_hist_kernel resolves from committed code and configuration:
    auto is the xla kernel, explicit values are honoured (f64 histograms
    force xla), and no file on disk takes part — a fresh checkout and a
    long-lived one train with the same kernel. chip_smoke.py is what proves
    on the chip that `mixed` still compiles."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.rand(400, 4)
    y = (X[:, 0] > 0.5).astype(float)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 7,
            "min_data_in_leaf": 10, "max_bin": 15}

    def resolved(**extra):
        params = dict(base, **extra)
        bst = lgb.Booster(params=params,
                          train_set=lgb.Dataset(X, label=y, params=params))
        return bst._gbdt.spec.hist_kernel

    assert resolved() == "xla"
    assert resolved(tpu_hist_kernel="auto") == "xla"
    assert resolved(tpu_hist_kernel="mixed") == "mixed"
    assert resolved(tpu_hist_kernel="pallas") == "pallas"
    assert resolved(tpu_hist_kernel="mixed", tpu_hist_f64=True) == "xla"
