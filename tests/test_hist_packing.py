"""Packed-row code layouts (u4 nibble / u6 six-bit) parity tests.

Reference analog: Dense4bitsBin (src/io/dense_nbits_bin.hpp:37) stores two
<=16-bin codes per byte; the "u6" layout additionally serves the reference's
GPU benchmark config max_bin=63 (docs/GPU-Performance.rst:105-125) at 3
bytes per 4 codes. Here the packing only affects the compacted-gather row
payload — histograms must be IDENTICAL across layouts, weight modes and
kernels: every compacted pass is held to the STREAMED pass over the same
pending leaves, the path that reads no row index and no packed rows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.grower import _slot_grouped_rows
from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.ops.histogram import (build_histograms, code_bytes_total,
                                        code_mode_for, pack_rows,
                                        unpack_codes)


@pytest.mark.parametrize("mode,max_code,F", [
    ("u4", 16, 8), ("u4", 16, 7),            # odd F exercises the pad lane
    ("u6", 64, 12), ("u6", 64, 10),
    ("u8", 256, 9), ("u16", 4096, 5),
])
def test_pack_unpack_roundtrip(mode, max_code, F):
    rng = np.random.RandomState(0)
    dtype = np.uint16 if mode == "u16" else np.uint8
    X = rng.randint(0, max_code, size=(256, F)).astype(dtype)
    g = rng.randn(256).astype(np.float32)
    h = np.abs(rng.randn(256)).astype(np.float32)
    inc = np.ones(256, np.float32)
    packed, ncb = pack_rows(jnp.asarray(X), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(inc), False, mode)
    assert ncb == code_bytes_total(F, mode)
    codes = np.asarray(unpack_codes(packed[:, :ncb], F, mode))
    np.testing.assert_array_equal(codes, X.astype(np.int64))


def test_code_mode_selection():
    assert code_mode_for(16, np.dtype(np.uint8)) == "u4"
    assert code_mode_for(63, np.dtype(np.uint8)) == "u6"
    assert code_mode_for(255, np.dtype(np.uint8)) == "u8"
    assert code_mode_for(300, np.dtype(np.uint16)) == "u16"


def _compacted(X, g, h, inc, leaf_id, slot_of_leaf, num_slots, exact, mode):
    """What the grower hands a compacted pass: the rows grouped by pending
    slot by its own one sort, the pending count, the rows a slot and the
    packed rows."""
    row_idx, counts = _slot_grouped_rows(slot_of_leaf[leaf_id], num_slots)
    packed, _ = pack_rows(X, g, h, inc, exact, mode)
    return dict(row_idx=row_idx, n_active=jnp.sum(counts),
                slot_counts=counts, packed=packed)


# the Pallas kernel reads plain byte layouts and bf16 hi/lo weights only
_LAYOUTS = [(m, "xla", e) for m in ("u4", "u6", "u8", "u16")
            for e in (False, True)] + [("u8", "pallas", False),
                                       ("u16", "pallas", False)]


@pytest.mark.parametrize("pending", ["all", "some"])
@pytest.mark.parametrize(
    "mode,kernel,exact", _LAYOUTS,
    ids=[f"{m}-{k}-{'f32' if e else 'hilo'}" for m, k, e in _LAYOUTS])
def test_compacted_histogram_matches_full_pass(mode, kernel, exact, pending,
                                               monkeypatch):
    """Compacted pass through the packed layout == streaming full pass: in
    every code mode, in both weight modes, through both kernels, with every
    row pending and with the rows of two leaves left out."""
    monkeypatch.setattr(ph, "_INTERPRET", True)
    rng = np.random.RandomState(3)
    N, F, S = 1024, 6, 4
    max_code, B = {"u4": (15, 16), "u6": (63, 64), "u8": (255, 256),
                   "u16": (299, 512)}[mode]
    X = jnp.asarray(rng.randint(0, max_code + 1, size=(N, F)),
                    jnp.uint16 if mode == "u16" else jnp.uint8)
    g = jnp.asarray(rng.randn(N), jnp.float32)
    h = jnp.asarray(np.abs(rng.randn(N)), jnp.float32)
    inc = jnp.asarray(rng.rand(N) < 0.9, jnp.float32)
    L = S if pending == "all" else S + 2
    leaf_id = jnp.asarray(rng.randint(0, L, size=N), jnp.int32)
    slot_of_leaf = jnp.where(jnp.arange(L + 1) < S, jnp.arange(L + 1), -1
                             ).astype(jnp.int32)
    args = (X, g * inc, h * inc, inc, leaf_id, slot_of_leaf)
    kw = dict(num_slots=S, num_bins_padded=B, chunk_rows=256)
    compacted = _compacted(*args, S, exact, mode)
    assert (int(compacted["n_active"]) == N) == (pending == "all")

    full = build_histograms(*args, exact=exact, compensated=exact, **kw)
    if kernel == "pallas":
        compact = ph.build_histograms_pallas(*args, **kw, **compacted)
    else:
        compact = build_histograms(*args, exact=exact, compensated=exact,
                                   code_mode=mode, **kw, **compacted)
    assert np.abs(np.asarray(full)).sum() > 0
    np.testing.assert_array_equal(np.asarray(full[..., 2]),
                                  np.asarray(compact[..., 2]))
    np.testing.assert_allclose(np.asarray(full), np.asarray(compact),
                               rtol=1e-6 if exact else 1e-5,
                               atol=1e-5 if exact else 1e-4)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_compacted_pass_requires_its_layout(kernel, monkeypatch):
    """One compacted layout a kernel: a row index comes with the rows a slot
    and the packed rows, or the pass is refused where it is traced."""
    monkeypatch.setattr(ph, "_INTERPRET", True)
    rng = np.random.RandomState(1)
    N, F, S, B = 512, 3, 2, 16
    X = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    ones = jnp.ones(N, jnp.float32)
    leaf_id = jnp.asarray(rng.randint(0, S, size=N), jnp.int32)
    slot_of_leaf = jnp.arange(S + 1, dtype=jnp.int32).at[S].set(-1)
    build = ph.build_histograms_pallas if kernel == "pallas" \
        else build_histograms
    whole = _compacted(X, ones, ones, ones, leaf_id, slot_of_leaf, S,
                       False, "u8")
    for missing in ("slot_counts", "packed"):
        part = {k: v for k, v in whole.items() if k != missing}
        with pytest.raises(AssertionError):
            build(X, ones, ones, ones, leaf_id, slot_of_leaf, num_slots=S,
                  num_bins_padded=B, chunk_rows=256, **part)


def test_hist_f64_precision():
    """tpu_hist_f64's build path (full-f32 weight columns at HIGHEST
    precision + Kahan chunk carry) must land far closer to an exact NumPy
    f64 histogram than the bf16 hi/lo default — the role of the reference's
    double HistogramBinEntry bins (bin.h:29-31). Thresholds are ~3x above
    measured (hilo ~1.6e-4, f64-mode ~5e-6 abs-vs-unit error, 34x apart)."""
    rng = np.random.RandomState(0)
    N, F, B, S = 1 << 16, 8, 64, 4
    X = jnp.asarray(rng.randint(0, B, size=(N, F)).astype(np.uint8))
    g = jnp.asarray(rng.randn(N).astype(np.float32))
    h = jnp.asarray(np.abs(rng.randn(N)).astype(np.float32))
    inc = jnp.asarray((rng.rand(N) < 0.9).astype(np.float32))
    leaf = jnp.asarray(rng.randint(0, S, size=N), jnp.int32)
    sol = jnp.arange(S, dtype=jnp.int32)

    Xn, incn, ln = np.asarray(X), np.asarray(inc), np.asarray(leaf)
    gw = np.asarray(g).astype(np.float64) * incn
    hw = np.asarray(h).astype(np.float64) * incn
    oracle = np.zeros((S, F, B, 3))
    for c, w in ((0, gw), (1, hw), (2, incn.astype(np.float64))):
        for f in range(F):
            for s in range(S):
                m = ln == s
                oracle[s, f, :, c] = np.bincount(Xn[m, f], weights=w[m],
                                                 minlength=B)

    def err(**kw):
        out = np.asarray(build_histograms(
            X, g * inc, h * inc, inc, leaf, sol, num_slots=S,
            num_bins_padded=B, chunk_rows=4096, **kw), np.float64)
        return np.max(np.abs(out - oracle) / np.maximum(np.abs(oracle), 1.0))

    e_hilo = err(exact=False)
    e_f64 = err(exact=True, compensated=True)
    assert e_hilo < 1e-3, e_hilo
    assert e_f64 < 2e-5, e_f64
    assert e_f64 < e_hilo / 10, (e_f64, e_hilo)


def test_hist_f64_compacted_matches_streaming():
    """The f32 weight channels survive the packed-row byte round-trip: a
    compacted f64-mode pass equals the streaming f64-mode pass exactly."""
    rng = np.random.RandomState(4)
    N, F, B, S = 2048, 6, 32, 4
    X = jnp.asarray(rng.randint(0, B, size=(N, F)), jnp.uint8)
    g = jnp.asarray(rng.randn(N), jnp.float32)
    h = jnp.asarray(np.abs(rng.randn(N)), jnp.float32)
    inc = jnp.ones(N, jnp.float32)
    leaf_id = jnp.asarray(rng.randint(0, S, size=N), jnp.int32)
    slot_of_leaf = jnp.arange(S + 1, dtype=jnp.int32).at[S].set(-1)

    full = build_histograms(X, g, h, inc, leaf_id, slot_of_leaf,
                            num_slots=S, num_bins_padded=B, chunk_rows=256,
                            exact=True, compensated=True)
    compact = build_histograms(
        X, g, h, inc, leaf_id, slot_of_leaf, num_slots=S, num_bins_padded=B,
        chunk_rows=256, exact=True, compensated=True,
        **_compacted(X, g, h, inc, leaf_id, slot_of_leaf, S, True, "u8"))
    np.testing.assert_allclose(np.asarray(full), np.asarray(compact),
                               rtol=1e-6, atol=1e-5)
