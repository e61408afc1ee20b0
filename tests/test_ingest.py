"""Device-side dataset ingest (tpu_ingest=device|auto; ops/ingest.py,
dataset.DeferredBinning, boosting/gbdt.py engagement).

Pins the tentpole contracts of the device-ingest PR:

- the jitted device bin kernel reproduces ``BinMapper.value_to_bin``
  BIT-exactly: exact-tie boundary values, NaN under both missing modes
  (zero_as_missing included), ±inf, -0.0, and categorical columns with
  negative / unseen / fractional raw values;
- in-trace packing (u4/u6/u8/u16) is byte-identical to the host
  ``pack_codes_host`` twin over the padded residency layout;
- one compile serves every chunk of a shape class, including the
  zero-masked tail chunk (traced row offset; RecompileGuard pin);
- end-to-end training from raw arrays under ``tpu_ingest=device`` is
  bit-identical to the host-binned path — serial AND sharded (8-device
  harness), through EFB's deferred planning, and across a checkpoint
  resume that flips the (checkpoint-VOLATILE) knob back to host;
- eligibility gates fall back loudly: f32-lossy f64, sparse input, int
  dtypes, oversized categorical tables, and the tpu_ingest=auto row
  threshold;
- the vectorized ``HostShardStore`` build (one reused staging buffer)
  produces the same packed shards + CRCs as the reference construction;
- ``_map_find_bin`` pins deterministic result-dict ordering, and
  ``BinMapper.default_bin`` is the one sanctioned zero-bin computation.
"""
import os
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import (_AUTO_DEFER_MIN_ROWS, _map_find_bin,
                                  bin_dense_host, construct_dataset)
from lightgbm_tpu.ops import ingest as ingest_mod
from lightgbm_tpu.ops.histogram import code_mode_for, unpack_codes
from lightgbm_tpu.ops.stream import HostShardStore, pack_codes_host


def _adversarial_matrix(n=3000, seed=3):
    """The parity torture matrix: ties, NaN, ±inf, -0.0, categorical with
    negative/unseen/fractional values."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8).astype(np.float32)
    X[:, 1] = np.round(X[:, 1] * 4) / 4                # heavy exact ties
    X[rng.rand(n) < 0.15, 2] = np.nan                  # NaN-bin path
    X[: n // 8, 3] = np.inf
    X[n // 8: n // 4, 3] = -np.inf
    X[n // 4: n // 2, 3] = -0.0
    X[rng.rand(n) < 0.3, 4] = 0.0                      # zero/default bin
    X[:, 5] = rng.randint(0, 12, n).astype(np.float32)  # categorical
    X[: n // 10, 5] = -3.0                             # negative category
    X[n // 10: n // 8, 5] = 97.0                       # unseen category
    X[n // 8: n // 6, 5] = 4.5                         # fractional -> trunc
    X[rng.rand(n) < 0.05, 5] = np.nan                  # categorical NaN
    y = (X[:, 0] > 0).astype(np.float32)
    return X, y


def _mappers_for(X, y, params=None, categorical=None):
    cfg = Config.from_params(dict({"max_bin": 63, "verbose": -1,
                                   "min_data_in_leaf": 5,
                                   "tpu_ingest": "host"}, **(params or {})))
    cd = construct_dataset(X, y, cfg,
                           categorical_features=categorical)
    return cd


def _device_codes(X, cd, n_pad, cols_pad, code_mode=None, chunk_rows=0):
    codes, rep = ingest_mod.device_ingest(
        X, cd.mappers, np.asarray(cd.real_feature_idx),
        n_rows=X.shape[0], n_rows_padded=n_pad, num_cols=cols_pad,
        out_dtype=cd.code_dtype, chunk_rows=chunk_rows,
        code_mode=code_mode)
    return np.asarray(codes), rep


def _host_padded(X, cd, n_pad, cols_pad):
    Xb = bin_dense_host(X, cd.mappers, np.asarray(cd.real_feature_idx),
                        cd.code_dtype, X.shape[0])
    ref = np.zeros((n_pad, cols_pad), cd.code_dtype)
    ref[: X.shape[0], : Xb.shape[1]] = Xb
    return ref


# ------------------------------------------------------- bit-exact parity

def test_device_matches_host_adversarial():
    """Ties, NaN, ±inf, -0.0, categorical (negative/unseen/fractional/NaN)
    — device codes equal the host oracle including row+column padding
    zeros."""
    X, y = _adversarial_matrix()
    cd = _mappers_for(X, y, categorical=[5])
    n_pad, cols_pad = X.shape[0] + 512, len(cd.real_feature_idx) + 3
    dev, rep = _device_codes(X, cd, n_pad, cols_pad, chunk_rows=700)
    ref = _host_padded(X, cd, n_pad, cols_pad)
    assert dev.dtype == ref.dtype
    assert np.array_equal(dev, ref)
    assert rep["compiles"] == 1


def test_device_matches_host_zero_as_missing():
    """zero_as_missing routes NaN through the zero search value on both
    sides — parity must hold under MISSING_ZERO mappers too."""
    X, y = _adversarial_matrix(seed=5)
    cd = _mappers_for(X, y, params={"zero_as_missing": True},
                      categorical=[5])
    n_pad, cols_pad = X.shape[0] + 256, len(cd.real_feature_idx)
    dev, _ = _device_codes(X, cd, n_pad, cols_pad)
    assert np.array_equal(dev, _host_padded(X, cd, n_pad, cols_pad))


def test_exact_boundary_values_tie_left():
    """Feed every f32-rounded bin boundary back through both paths: the
    side='left' tie rule must agree bin-for-bin (the f32-floor threshold
    construction is exactly what makes this hold)."""
    rng = np.random.RandomState(11)
    X = rng.randn(4000, 3).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    cd = _mappers_for(X, y, params={"max_bin": 255})
    cols = []
    for m in cd.mappers:
        ub = np.asarray(m.bin_upper_bound, np.float64)
        b = ub[np.isfinite(ub)].astype(np.float32)
        reps = int(np.ceil(4000 / max(len(b), 1)))
        cols.append(np.tile(b, reps)[:4000])
    Xt = np.stack(cols, axis=1).astype(np.float32)
    n_pad = 4096
    dev, _ = _device_codes(Xt, cd, n_pad, 3)
    assert np.array_equal(dev, _host_padded(Xt, cd, n_pad, 3))


@pytest.mark.parametrize("max_bin,expect_modes", [
    (15, ("u4",)), (63, ("u6", "u8")), (255, ("u8",)), (400, ("u16",))])
def test_packed_layouts_match_host(max_bin, expect_modes):
    """In-trace packing equals pack_codes_host byte-for-byte over the
    padded layout, and round-trips through unpack_codes."""
    rng = np.random.RandomState(13)
    X = rng.rand(1500, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    cd = _mappers_for(X, y, params={"max_bin": max_bin})
    max_code = max(int(m.num_bin) for m in cd.mappers) - 1
    mode = code_mode_for(max_code, cd.code_dtype)
    assert mode in expect_modes
    n_pad, cols_pad = 1792, 8
    packed_dev, _ = _device_codes(X, cd, n_pad, cols_pad, code_mode=mode)
    ref = _host_padded(X, cd, n_pad, cols_pad)
    packed_host = pack_codes_host(ref, mode)
    assert np.array_equal(packed_dev, packed_host)
    assert np.array_equal(
        np.asarray(unpack_codes(packed_dev, cols_pad, mode)), ref)


def test_f64_lossless_input_matches():
    """f64 input that survives the f32 round trip bins identically (the
    blocker admits exactly this class)."""
    rng = np.random.RandomState(17)
    X = rng.randint(-500, 500, (2000, 4)).astype(np.float64) / 8.0
    y = (X[:, 0] > 0).astype(np.float32)
    assert ingest_mod.f32_lossless(X)
    cd = _mappers_for(X, y)
    dev, _ = _device_codes(X, cd, 2048, 4)
    assert np.array_equal(dev, _host_padded(X, cd, 2048, 4))


# ------------------------------------------------- compile + chunk economy

def test_one_compile_for_all_chunks_including_tail():
    """The row offset is traced: 7 full chunks + a zero-masked tail chunk
    share ONE executable, and a warmed ingestor adds zero cache misses
    (the RecompileGuard pin)."""
    from lightgbm_tpu.analysis.guards import RecompileGuard
    X, y = _adversarial_matrix(n=2000)
    cd = _mappers_for(X, y, categorical=[5])
    C = len(cd.real_feature_idx)
    import jax
    ing = ingest_mod.DeviceIngestor(cd.mappers, num_cols=C, n_rows=2000,
                                    out_dtype=cd.code_dtype)
    # warm through the feeder's own placement path: committed-array
    # shardings are part of the jit cache key
    ing.bin_chunk(jax.device_put(np.zeros((256, C), np.float32)), 0)
    guard = RecompileGuard(label="ingest-test")
    guard.register(ing._fn, "ingest_bin")
    with guard:
        guard.mark_warm()
        codes, rep = ingest_mod.device_ingest(
            X, cd.mappers, np.asarray(cd.real_feature_idx), n_rows=2000,
            n_rows_padded=2304, num_cols=C, out_dtype=cd.code_dtype,
            chunk_rows=256, ingestor=ing)
    assert rep["n_chunks"] == 9
    assert ing.compiles == 1
    assert guard.report()["post_warmup_cache_misses"] == 0
    assert np.array_equal(np.asarray(codes), _host_padded(X, cd, 2304, C))


def test_resolve_chunk_rows_contract():
    assert ingest_mod.resolve_chunk_rows(5000, 100000, 16) == 5000
    auto = ingest_mod.resolve_chunk_rows(0, 10 ** 9, 28)
    assert ingest_mod._CHUNK_MIN <= auto <= ingest_mod._CHUNK_MAX
    assert auto % 256 == 0
    # never exceeds the padded row count
    assert ingest_mod.resolve_chunk_rows(0, 1000, 28) == 1000


def test_chunk_feeder_stall_accounting():
    """Disabled prefetch turns every transfer into a counted stall; enabled
    prefetch turns them into hits."""
    X = np.random.RandomState(0).rand(1024, 4).astype(np.float32)
    idx = np.arange(4)
    os.environ["LGBM_TPU_INGEST_NO_PREFETCH"] = "1"
    try:
        f = ingest_mod.ChunkFeeder(X, idx, chunk_rows=256, n_chunks=4,
                                   num_cols=4)
        for i in range(4):
            f.prefetch(i)
            f.get(i)
        assert f.stalls == 4 and f.hits == 0
    finally:
        os.environ.pop("LGBM_TPU_INGEST_NO_PREFETCH", None)
    f = ingest_mod.ChunkFeeder(X, idx, chunk_rows=256, n_chunks=4,
                               num_cols=4)
    for i in range(4):
        f.prefetch(i)
        f.get(i)
    assert f.hits == 4 and f.stalls == 0
    assert f.bytes_h2d == 4 * 256 * 4 * 4


# ----------------------------------------------------------- eligibility

def test_blocker_gates():
    m = _mappers_for(np.random.RandomState(0).rand(500, 2).astype(
        np.float32), np.zeros(500, np.float32)).mappers
    ok32 = np.zeros((8, 2), np.float32)
    assert ingest_mod.device_ingest_blocker(ok32, m) is None
    lossy = np.full((8, 2), 0.1, np.float64)      # 0.1 is not f32-exact
    assert "lossless" in ingest_mod.device_ingest_blocker(lossy, m)
    ints = np.zeros((8, 2), np.int32)
    assert "dtype" in ingest_mod.device_ingest_blocker(ints, m)
    sp = pytest.importorskip("scipy.sparse")
    assert "sparse" in ingest_mod.device_ingest_blocker(
        sp.csr_matrix(ok32), m)


def test_f32_lossless_probe():
    assert ingest_mod.f32_lossless(np.random.rand(100, 3).astype(np.float32))
    exact = np.arange(3000, dtype=np.float64).reshape(1000, 3)
    assert ingest_mod.f32_lossless(exact)
    exact[500, 1] = 0.1
    assert not ingest_mod.f32_lossless(exact)
    nan_ok = exact.copy()
    nan_ok[500, 1] = np.nan
    assert ingest_mod.f32_lossless(nan_ok)


def test_auto_defers_only_at_scale():
    """tpu_ingest=auto defers at >= _AUTO_DEFER_MIN_ROWS dense f32 rows;
    below it (and for blocked input) construction bins on host."""
    rng = np.random.RandomState(2)
    small = rng.rand(1000, 4).astype(np.float32)
    ys = np.zeros(1000, np.float32)
    cfg = Config.from_params({"verbose": -1, "tpu_ingest": "auto"})
    assert not construct_dataset(small, ys, cfg).deferred
    big = rng.rand(_AUTO_DEFER_MIN_ROWS, 4).astype(np.float32)
    yb = np.zeros(_AUTO_DEFER_MIN_ROWS, np.float32)
    cd = construct_dataset(big, yb, cfg)
    assert cd.deferred
    # bin_rows serves samples WITHOUT materializing the host matrix ...
    rows = np.array([0, 17, 65535])
    got = cd.bin_rows(rows)
    assert cd._X_binned is None
    # ... and lazy materialization is the host oracle bit-for-bit
    full = cd.X_binned
    assert np.array_equal(got, full[rows])
    assert np.array_equal(
        full, bin_dense_host(big, cd.mappers,
                             np.asarray(cd.real_feature_idx),
                             cd.code_dtype, big.shape[0]))


def test_explicit_device_falls_back_on_lossy_f64():
    """tpu_ingest=device on inadmissible input must not crash — it warns
    and bins on host, and training still works."""
    rng = np.random.RandomState(4)
    X = rng.rand(800, 4)                       # f64, not f32-representable
    y = (X[:, 0] > 0.5).astype(np.float32)
    p = dict(objective="binary", num_leaves=7, verbose=-1,
             min_data_in_leaf=5, tpu_ingest="device")
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=2,
                    keep_training_booster=True)
    assert bst._gbdt._ingest_report is None
    assert np.isfinite(bst.predict(X)).all()


# ------------------------------------------------- end-to-end bit identity

_TRAIN = dict(objective="binary", num_leaves=15, learning_rate=0.1,
              min_data_in_leaf=5, verbose=-1, deterministic=True)


def _train(X, y, ingest, extra=None, rounds=8):
    extra = dict(extra or {})
    cats = extra.pop("_cats", "auto")
    p = dict(_TRAIN, tpu_ingest=ingest, **extra)
    ds = lgb.Dataset(X.copy(), label=y.copy(), params=p,
                     categorical_feature=cats)
    return lgb.train(p, ds, num_boost_round=rounds,
                     keep_training_booster=True)


def test_e2e_training_bit_identity_serial():
    """The acceptance pin: training from raw arrays under
    tpu_ingest=device is bit-identical to the host-binned path — placed
    codes, predictions, and the serialized model."""
    X, y = _adversarial_matrix(n=3000)
    bh = _train(X, y, "host", {"_cats": [5]})
    bd = _train(X, y, "device", {"_cats": [5]})
    assert bd._gbdt._ingest_report is not None
    assert bd._gbdt._ingest_report["compiles"] == 1
    assert np.array_equal(np.asarray(bh._gbdt.Xb), np.asarray(bd._gbdt.Xb))
    assert np.array_equal(bh.predict(X), bd.predict(X))
    assert bh.model_to_string() == bd.model_to_string()


@pytest.mark.slow
def test_e2e_sharded_placement_identity():
    """8-device data-parallel: every device ingests its own row block and
    the shards assemble into the row mesh — placement and training stay
    bit-identical to the host path."""
    rng = np.random.RandomState(21)
    X = rng.rand(4096, 10).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    extra = {"tree_learner": "data", "num_machines": 1}
    bh = _train(X, y, "host", dict(extra))
    bd = _train(X, y, "device", dict(extra))
    assert bd._gbdt._ingest_report["devices"] == 8
    assert bd._gbdt._ingest_report["compiles"] == 1
    xh, xd = bh._gbdt.Xb, bd._gbdt.Xb
    assert np.array_equal(np.asarray(xh), np.asarray(xd))
    assert xh.sharding.is_equivalent_to(xd.sharding, xh.ndim)
    assert np.array_equal(bh.predict(X), bd.predict(X))


def test_efb_deferred_planning_identity():
    """A flags-shaped dataset where EFB engages: the deferred path plans
    from bin_rows(sample_row_indices(N)) and must land the identical
    bundling + model as planning from the materialized matrix."""
    rng = np.random.RandomState(7)
    g, p = 5, 10
    flags = np.zeros((3000, g * p), np.float32)
    picks = rng.randint(0, p, size=(3000, g))
    for gi in range(g):
        flags[np.arange(3000), gi * p + picks[:, gi]] = 1.0
    yf = (picks[:, 0] % 2).astype(np.float32)
    bh = _train(flags, yf, "host")
    bd = _train(flags, yf, "device")
    assert bh._gbdt.bundle is not None and bd._gbdt.bundle is not None
    assert np.array_equal(np.asarray(bh._gbdt.bundle.col),
                          np.asarray(bd._gbdt.bundle.col))
    assert np.array_equal(bh.predict(flags), bd.predict(flags))
    assert bh.model_to_string() == bd.model_to_string()


def test_checkpoint_resume_across_ingest_modes():
    """tpu_ingest is checkpoint-VOLATILE: a snapshot trained under device
    ingest resumes under host ingest (and vice versa) bit-identically —
    the fingerprint hashes the CODES, not where they were computed."""
    X, y = _adversarial_matrix(n=2500, seed=9)
    bd = _train(X, y, "device", {"_cats": [5]}, rounds=4)
    ck = tempfile.mkdtemp(prefix="lgbm_ingest_ck_")
    try:
        bd.save_checkpoint(ck)
        p = dict(_TRAIN, tpu_ingest="host")
        ds = lgb.Dataset(X.copy(), label=y.copy(), params=p,
                         categorical_feature=[5])
        bh = lgb.Booster(params=p, train_set=ds)
        bh.resume(ck)
        for _ in range(3):
            bd.update()
            bh.update()
        assert np.array_equal(bd.predict(X), bh.predict(X))
    finally:
        import shutil
        shutil.rmtree(ck, ignore_errors=True)


# ------------------------------------- host-side satellites (this PR)

def test_map_find_bin_deterministic_order():
    """The thread-pooled find-bin fan-out pins result-dict ordering to the
    ACTIVE list order regardless of completion order."""
    import time as _t
    active = [5, 0, 3, 9, 1]

    def find_one(j):
        _t.sleep(0.002 * (5 - (j % 5)))        # finish out of order
        return j * 10

    got = _map_find_bin(active, find_one)
    assert list(got.keys()) == active
    assert got == {j: j * 10 for j in active}
    # the serial (<=1 worker) path agrees
    assert _map_find_bin([2], lambda j: j + 1) == {2: 3}


def test_default_bin_is_the_one_zero_bin():
    """Satellite pin: BinMapper.default_bin equals value_to_bin(0) for
    every mapper — consumers read the attribute instead of re-running the
    mapper per column."""
    X, y = _adversarial_matrix(n=1500)
    cd = _mappers_for(X, y, categorical=[5])
    for m in cd.mappers:
        assert m.default_bin == int(m.value_to_bin(np.zeros(1))[0])


def test_value_to_bin_out_parameter():
    """The single-pass host path: value_to_bin(col, out=...) writes the
    identical codes into the target dtype as the int32 return path."""
    X, y = _adversarial_matrix(n=1200)
    cd = _mappers_for(X, y, categorical=[5])
    for inner, real in enumerate(cd.real_feature_idx):
        m = cd.mappers[inner]
        col = X[:, real]
        ref = m.value_to_bin(col)
        out = np.empty(1200, cd.code_dtype)
        ret = m.value_to_bin(col, out=out)
        assert ret is out
        assert np.array_equal(out, ref.astype(cd.code_dtype))


# --------------------------------------- stream-shard store vectorization

@pytest.mark.parametrize("code_mode,dtype,hi", [
    ("u8", np.uint8, 250), ("u16", np.uint16, 400),
    ("u4", np.uint8, 15), ("u6", np.uint8, 60)])
def test_shard_store_matches_reference(code_mode, dtype, hi):
    """The single-reused-buffer shard build equals the obvious reference
    construction (per-device padded blocks + concatenate + pack) for every
    packed layout, shard CRCs verify, and the device unpack round-trips."""
    rng = np.random.RandomState(31)
    n_real, f_real = 900, 5
    n_pad, cols, R, ndev = 1024, 7, 128, 2
    X = rng.randint(0, hi + 1, (n_real, f_real)).astype(dtype)
    store = HostShardStore(X, n_rows_padded=n_pad, num_cols=cols,
                           local_shard_rows=R, n_devices=ndev,
                           code_mode=code_mode)
    per_dev = n_pad // ndev

    def padded_block(a, b):
        out = np.zeros((b - a, cols), dtype)
        if a < n_real:
            rows = X[a:min(b, n_real)]
            out[: rows.shape[0], :f_real] = rows
        return out

    assert store.n_shards == per_dev // R
    for i in range(store.n_shards):
        block = np.concatenate([padded_block(d * per_dev + i * R,
                                             d * per_dev + (i + 1) * R)
                                for d in range(ndev)])
        ref = np.ascontiguousarray(pack_codes_host(block, code_mode))
        assert np.array_equal(store.shards[i], ref)
        assert store.verify_shard(i)
        # shards are materialized copies, not views of the staging buffer
        assert store.shards[i].base is None
        assert np.array_equal(
            np.asarray(unpack_codes(store.shards[i], cols, code_mode)),
            block)
