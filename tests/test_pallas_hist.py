"""Pallas histogram kernel vs the XLA one-hot matmul — the analog of the
reference's GPU_DEBUG_COMPARE cross-check (gpu_tree_learner.cpp:1018-1043),
run in Pallas interpret mode on the CPU test backend.

The reference of every COMPACTED pass here is the STREAMED xla pass over
the same pending leaves: the independent path (no row index, no packed
rows, no gather), not a second compacted layout."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.grower import _slot_grouped_rows
from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.ops.histogram import build_histograms, pack_rows


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


def _compacted(X, g, h, inc, leaf_id, slot_of_leaf, num_slots):
    """What the grower hands a compacted pass: the rows grouped by pending
    slot by its own one sort, the pending count, the rows a slot, and the
    packed rows (bf16 hi/lo weights, plain byte codes)."""
    row_idx, counts = _slot_grouped_rows(slot_of_leaf[leaf_id], num_slots)
    packed, _ = pack_rows(X, g, h, inc, exact=False)
    return dict(row_idx=row_idx, n_active=jnp.sum(counts),
                slot_counts=counts, packed=packed)


def _assert_same_histograms(out, ref):
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    # count channel must be exact
    np.testing.assert_array_equal(np.asarray(out[..., 2]),
                                  np.asarray(ref[..., 2]))


def test_pallas_matches_xla_full_pass():
    X, g, h, inc, leaf_id = _data()
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[jnp.arange(4)].set(
        jnp.arange(4))
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024)
    packed, _ = pack_rows(X, g, h, inc, exact=False)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=S, num_bins_padded=B,
                                     chunk_rows=1024, packed=packed)
    _assert_same_histograms(out, ref)


def test_pallas_matches_xla_compacted():
    X, g, h, inc, leaf_id = _data(seed=2)
    S, B = 4, 32
    # only leaves 1 and 3 pending -> ~1/4 of rows active
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[1].set(0).at[3].set(1)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024)
    out = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=1024, **_compacted(X, g, h, inc, leaf_id, slot_of_leaf, S))
    _assert_same_histograms(out, ref)


def test_slot_grouped_position_slots_match():
    """The compacted layout: rows grouped by slot, a position's slot from the
    running sum of the rows a slot — must equal the streamed pass, whose
    slots come from a per-row lookup of the leaf, in BOTH kernels."""
    X, g, h, inc, leaf_id = _data(seed=7)
    S, B = 4, 32
    slot_of_leaf = jnp.full(9, -1, jnp.int32).at[1].set(0).at[3].set(1).at[5].set(2)
    compacted = _compacted(X, g, h, inc, leaf_id, slot_of_leaf, S)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=1024)
    grouped = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf,
                               num_slots=S, num_bins_padded=B,
                               chunk_rows=1024, **compacted)
    _assert_same_histograms(grouped, ref)
    grouped_pl = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=1024, **compacted)
    _assert_same_histograms(grouped_pl, ref)


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


def test_pallas_f32_precision_vs_f64():
    """hi/lo bf16 channels keep ~f32 accuracy on large sums."""
    X, g, h, inc, leaf_id = _data(n=8192, f=2, bins=8, leaves=1, seed=3)
    slot_of_leaf = jnp.zeros(2, jnp.int32)
    packed, _ = pack_rows(X, g, h, inc, exact=False)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=1, num_bins_padded=8,
                                     chunk_rows=2048, packed=packed)
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
    from lightgbm_tpu.ops.histogram import code_bytes, unpack_codes
    rng = np.random.RandomState(11)
    n, f, bins = 2048, 5, 500
    X = jnp.asarray(rng.randint(0, bins, size=(n, f)).astype(np.uint16))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32))
    inc = jnp.ones(n, jnp.float32)
    assert code_bytes(X.dtype) == 2
    packed, ncb = pack_rows(X, g, h, inc, exact=False)
    codes = unpack_codes(packed[:, :ncb], f, "u16")
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(X, np.int32))

    leaf_id = jnp.asarray(rng.randint(0, 4, size=n).astype(np.int32))
    slot_of_leaf = jnp.full(5, -1, jnp.int32).at[1].set(0).at[3].set(1)
    B = 512
    compacted = _compacted(X, g, h, inc, leaf_id, slot_of_leaf, 2)
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=2,
                           num_bins_padded=B, chunk_rows=512)
    cmp = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=2,
                           num_bins_padded=B, chunk_rows=512, **compacted)
    _assert_same_histograms(cmp, ref)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=2, num_bins_padded=B,
                                     chunk_rows=512, packed=packed)
    _assert_same_histograms(out, ref)
    out = ph.build_histograms_pallas(X, g, h, inc, leaf_id, slot_of_leaf,
                                     num_slots=2, num_bins_padded=B,
                                     chunk_rows=512, **compacted)
    _assert_same_histograms(out, ref)


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
    ref = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S,
                           num_bins_padded=B, chunk_rows=512)
    capped = ph.build_histograms_pallas(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=512, max_rows=X.shape[0] // 4,
        **_compacted(X, g, h, inc, leaf_id, slot_of_leaf, S))
    _assert_same_histograms(capped, ref)


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
