"""``tree_learner=data`` as the benchmark's four-chip cell runs it
(``criteo67-255-dp4-train``, ISSUE 34), on four of the harness's virtual
CPU devices: the shards are tied to the table (their local histograms sum
to the serial one), the collectives' byte counters are the per-wave payloads
times the waves the loop counted, each shard's passes are published, the
step syncs with the host nowhere, the per-device ingest reports itself, the
cell's job refuses a program that widens float32 rows, and the whole cell
rehearsed at a tiny size reads ``correct``.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "criteo67-255-dp4-train"
D = 4
PARAMS = dict(objective="binary", num_leaves=15, max_bin=63, min_data_in_leaf=20,
              learning_rate=0.2, verbose=-1, metric="none", device="cpu", seed=3,
              tree_learner="data", num_machines=D)


def _data(n=70000, f=10, seed=4):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.2 * rng.randn(n) > 0.9).astype(np.float32)
    return X, y


@pytest.fixture
def registry():
    obs.reset_for_tests()
    yield obs.get_registry()
    obs.reset_for_tests()


@pytest.fixture
def booster(registry):
    X, y = _data()
    bst = lgb.Booster(params=dict(PARAMS),
                      train_set=lgb.Dataset(X, label=y, params=PARAMS))
    for _ in range(3):
        bst.update()
    bst._ensure_finalized()
    return bst


# ------------------------------------------------ the shards and the table

def test_shard_histograms_sum_to_the_serial_histogram():
    """Four shards' LOCAL histograms of a wave add up to the serial
    histogram of the same rows, and ``reduce_hist`` leaves each device the
    global sums of its own feature block."""
    from lightgbm_tpu.ops.histogram import build_histograms
    from lightgbm_tpu.parallel.comm import DataParallelComm
    N, F, B, S, L = 8192, 8, 32, 3, 7
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.randint(0, B, (N, F)).astype(np.uint8))
    # quarters and halves: every partial sum is exact in float32
    grad = jnp.asarray(rng.randint(-4, 5, N).astype(np.float32) / 4)
    hess = jnp.asarray(rng.randint(1, 5, N).astype(np.float32) / 2)
    included = jnp.asarray((rng.rand(N) < 0.9).astype(np.float32))
    leaf_id = jnp.asarray(rng.randint(0, L, N).astype(np.int32))
    slot_of_leaf = jnp.asarray(np.array([0, -1, 1, -1, 2, -1, -1, -1], np.int32))
    comm = DataParallelComm("rows", D, F)

    def local(X, g, h, inc, lid):
        hist = build_histograms(X, g * inc, h * inc, inc, lid, slot_of_leaf,
                                num_slots=S, num_bins_padded=B, chunk_rows=512)
        return hist[None], comm.reduce_hist(hist)[None]

    mesh = Mesh(np.array(jax.devices()[:D]), ("rows",))
    rows = P("rows")
    per_shard, reduced = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("rows", None), rows, rows, rows, rows),
        out_specs=(rows, rows), check_vma=False))(X, grad, hess, included, leaf_id)
    serial = build_histograms(
        X, grad * included, hess * included, included, leaf_id, slot_of_leaf,
        num_slots=S, num_bins_padded=B, chunk_rows=512)
    per_shard, reduced, serial = map(np.asarray, (per_shard, reduced, serial))
    assert per_shard.shape == (D, S, F, B, 3)
    assert serial[:, 0, :, 2].sum() == float(np.asarray(included)[
        np.isin(np.asarray(leaf_id), [0, 2, 4])].sum())
    assert np.array_equal(per_shard.sum(axis=0), serial)
    # device d holds the global sums of features [d * F/D, (d + 1) * F/D)
    assert np.array_equal(np.concatenate(list(reduced), axis=1), serial)


# --------------------------------------------------- what the loop counted

def test_comm_bytes_are_the_per_wave_payloads_times_the_waves(booster, registry):
    snap = obs.snapshot()
    per_wave = {k.split("comm.bytes_per_wave.")[1]: v
                for k, v in snap["gauges"].items()
                if k.startswith("comm.bytes_per_wave.")}
    assert set(per_wave) == {"psum_scatter_hist", "allgather_splits",
                             "psum_root_scalars"}
    waves = registry.summary("grow.waves").values()
    assert len(waves) == 3 and min(waves) >= 2
    for name in ("psum_scatter_hist", "allgather_splits"):
        assert registry.summary("comm.bytes." + name).values() == \
            [per_wave[name] * w for w in waves]
    # the root's three scalars: once a tree
    assert registry.summary("comm.bytes.psum_root_scalars").values() == [12.0] * 3
    assert registry.summary("comm.collectives_per_tree").values() == \
        [2 * w + 1 for w in waves]


def test_serial_growth_counts_no_collective(registry):
    X, y = _data(20000)
    params = dict(PARAMS, tree_learner="serial", num_machines=1)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
    bst.update()
    bst._ensure_finalized()
    names = obs.snapshot()["summaries"]
    assert not [n for n in names if n.startswith("comm.")
                or n.startswith("grow.stream_passes.")]


def test_each_shards_passes_are_published(booster, registry):
    waves = registry.summary("grow.waves").values()
    for d in range(D):
        streamed = registry.summary(f"grow.stream_passes.{d}").values()
        compacted = registry.summary(f"grow.compact_passes.{d}").values()
        assert [s + c for s, c in zip(streamed, compacted)] == waves
        assert min(streamed) >= 1            # the root streams on every shard
    # a wave counts as streamed when any shard streamed it
    for t, streamed in enumerate(registry.summary("grow.stream_passes").values()):
        assert streamed >= max(registry.summary(f"grow.stream_passes.{d}").values()[t]
                               for d in range(D))


def test_shards_that_take_different_arms_are_seen():
    """``wave_totals`` on a record in which shard 1 compacted a wave the
    others streamed."""
    from lightgbm_tpu.grower import WaveStats, wave_totals
    compacted = np.zeros((D, 14), bool)
    compacted[:, 1:3] = True
    compacted[1, 3] = True
    stats = WaveStats(waves=np.full(D, 4, np.int32),
                      rows_active=np.full((D, 14), 100, np.int32),
                      compacted=compacted,
                      rows_split=np.zeros((D, 14), np.int32),
                      scan_pending=np.zeros((D, 14), np.int32))
    t = wave_totals(stats, rows_per_device=1024, chunk_rows=256, hist_slots=3)
    assert t["shard_passes"] == [(2, 2), (1, 3), (2, 2), (2, 2)]
    assert (t["stream_passes"], t["compact_passes"]) == (2, 2)


@pytest.mark.parametrize("case", ["serial", "data", "data_bagged", "data_batch4"])
def test_counts_past_float32_are_taken_again_as_integers(case, monkeypatch, registry):
    """Past 2^24 rows a tree's float32 counts are no longer whole (119
    nodes of four trees off by a row or two in the four-chip cell's first
    chip run): the tree's counts are then taken again in int32 from where
    the rows ended up. With the threshold lowered to this table's size the
    recount gives what the float32 counts give here, where they are exact."""
    from lightgbm_tpu import grower
    X, y = _data(20000)
    params = dict(PARAMS)
    if case == "serial":
        params.update(tree_learner="serial", num_machines=1)
    if case == "data_bagged":
        params.update(bagging_fraction=0.6, bagging_freq=1)
    if case == "data_batch4":
        params.update(tree_batch=4)

    def train():
        bst = lgb.Booster(params=params,
                          train_set=lgb.Dataset(X, label=y, params=params))
        for _ in range(4):
            bst.update()
        return bst

    plain = train()
    monkeypatch.setattr(grower, "_F32_EXACT_ROWS", 1000)
    recounted = train()
    assert plain._gbdt.models[0][0].leaf_count.dtype == jnp.float32
    tree = recounted._gbdt.models[0][0]
    assert tree.leaf_count.dtype == tree.internal_count.dtype == jnp.int32
    assert recounted.model_to_string() == plain.model_to_string()
    for t in recounted.trees:
        assert t.internal_count[0] == t.leaf_count[: t.num_leaves].sum()
    recounted._ensure_finalized()
    if case != "serial":
        moved = registry.summary("comm.bytes.psum_leaf_counts").values()
        assert moved and set(moved) == {(PARAMS["num_leaves"] + 1) * 4.0}


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_the_step_is_one_executable_and_syncs_nowhere(learner, registry):
    """No host sync inside ``update()`` under either learner (the one
    MULTICHIP_r06 counted at d >= 2 was its own drain: ``np.asarray`` of a
    sharded score goes through ``__array__``, of a one-device score through
    the buffer protocol), and one executable a booster: the step's counter
    and shrinkage are placed like its outputs before the first call."""
    from lightgbm_tpu.analysis.guards import RecompileGuard
    X, y = _data(20000)
    params = dict(PARAMS, tree_learner=learner,
                  num_machines=D if learner == "data" else 1)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
    bst.update()
    guard = RecompileGuard(label=learner, disallow_transfers=True)
    guard.register(bst._gbdt._step_fn, "train_step")
    with guard:
        guard.mark_warm()
        for _ in range(3):
            bst.update()
        jax.block_until_ready(bst._gbdt.score)
    assert guard.report()["host_syncs"] == 0
    assert bst._gbdt._step_fn._cache_size() == 1
    assert obs.snapshot()["counters"]["compile.step_executables"] == 1


def test_every_device_ingests_its_block_and_reports_it(booster):
    report = booster._gbdt._ingest_report
    assert report["devices"] == D and report["in_turn"] is True
    assert len(report["device_seconds"]) == D
    assert report["seconds"] == pytest.approx(sum(report["device_seconds"]), abs=1e-5)
    gauges = obs.snapshot()["gauges"]
    assert [gauges[f"setup.ingest_device_s.{d}"] for d in range(D)] == \
        report["device_seconds"]


# --------------------------------------------------------- the cell's job

@pytest.fixture
def job(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import run as harness
    return harness, harness.load_module(
        os.path.join(BENCH, "jobs", "train_dp.py"), "job_train_dp_test")


def _probe_ctx(harness):
    ctx = harness.resolve_cell(CELL)
    return dict(ctx, seed=2147483659, log=lambda msg: None)


def test_probe_passes_on_this_program(job, registry):
    harness, train_dp = job
    train_dp.probe(_probe_ctx(harness))


@pytest.mark.parametrize("fault", ["holds_float64", "ran_the_round_trip_check"])
def test_probe_refuses_a_program_that_widens(job, registry, monkeypatch, fault):
    harness, train_dp = job

    class Widening(lgb.Dataset):
        def __init__(self, data, **kw):
            super().__init__(data, **kw)
            if fault == "holds_float64":
                self.raw_data = self.raw_data.astype(np.float64)
            else:
                with obs.setup_span("dataset.lossless_check"):
                    pass

    monkeypatch.setattr(lgb, "Dataset", Widening)
    with pytest.raises(SystemExit) as refused:
        train_dp.probe(_probe_ctx(harness))
    assert refused.value.code not in (0, None)
    assert "do not fit a run" in str(refused.value.code)


def test_the_jobs_table_is_datagens_to_the_bit(job):
    harness, train_dp = job
    from lib import datagen
    data = dict(harness.resolve_cell(CELL)["config"]["data"], block_rows=1024)
    for seed in (1, 2147483659):
        Xa, ya = datagen.generate(data, 10000, seed)
        Xb, yb = train_dp.generate(data, 10000, seed)
        assert np.array_equal(Xa, Xb) and np.array_equal(ya, yb)


def test_the_cells_floor_is_one_chips(job):
    """``work.root_floor_s`` of the four-chip cell is the floor of a shard
    on one chip: the shares of a peak built on it read as on one chip."""
    harness, _ = job
    from lib import peaks, work
    cfg = harness.resolve_cell(CELL)["config"]
    rows, chips = cfg["data"]["rows"], cfg["params"]["num_machines"]
    assert (rows, chips) == (44040192, 4) and rows % (chips * 65536) == 0
    pk = peaks.peaks_for("TPU v5 lite")
    shard, _ = work.root_pass_floor_s(rows // chips, 67, 255, pk)
    whole, _ = work.root_pass_floor_s(rows, 67, 255, pk)
    assert shard == pytest.approx(whole / chips)


def test_rehearsal_of_the_cell_reads_correct():
    """``benchmarks/rehearse.py tiny`` on four forced host devices: the whole
    run of the cell (probe, set-up, window, reference over the one table,
    comparison) at a tiny size. Four shards give what one table gives."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "tiny", CELL],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout[done.stdout.index("{"):])
    assert result["REHEARSAL_ON_CPU"] and result["correct"] is True
    assert result["compared"]["count_mismatch"]["value"] == 0.0
