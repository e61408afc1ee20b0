"""The wave loop measures itself (grower.WaveStats; docs/Observability.md):
exact per-wave counters carried in the loop's state, fetched with the
trees and published as ``grow.*`` / ``rows.routed`` / ``hist.mxu_flops``;
program spans on the profiler's clock; the retrace counter.

The counters are checked against an INDEPENDENT NumPy replay of the
finished tree over the binned rows: which wave split which node, which
leaves were pending, how many rows each held.
"""
import glob
import os

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.analysis.guards import RecompileGuard
from lightgbm_tpu.grower import WaveStats, wave_totals


@pytest.fixture
def clean_registry():
    obs.reset_for_tests()
    yield obs
    obs.reset_for_tests()


def _data(n=3000, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + X[:, 2] * X[:, 3]
         + 0.2 * rng.randn(n) > 0.3).astype(np.float32)
    return X, y


BASE = dict(objective="binary", num_leaves=15, max_bin=31, learning_rate=0.2,
            min_data_in_leaf=5, verbose=-1, metric="none", seed=11,
            tpu_hist_chunk=256)


# the widths of the default rule's two sides: 8 columns x 32 bins puts the
# break-even far under a half, 64 x 256 (74-byte packed rows) above it
NARROW = dict(f=8, max_bin=31)
WIDE = dict(f=64, max_bin=255)


def _booster(params, n=3000, rounds=0, f=8):
    X, y = _data(n, f)
    bst = lgb.Booster(params=dict(params),
                      train_set=lgb.Dataset(X, label=y, params=params))
    for _ in range(rounds):
        bst.update()
    return bst


# ----------------------------------------------- (b) replay of a finished tree

def _replay(tree, Xb, real, L, frac):
    """Waves of one finished tree, from the tree and the binned rows alone.

    With ``wave_size`` and ``hist_slots`` above ``num_leaves`` no cap binds
    before the leaf budget, so every leaf that is ever split is split in
    the first wave after the one that made it: a node's wave is its
    parent's plus one. A wave histograms the SMALLER child (by real rows;
    ties go left) of every node the wave before split. Growth that stops
    on gain, with budget left, runs one last wave that splits nothing.
    Rows are counted raw (padding rows route too, down the left)."""
    n_nodes = int(tree.num_leaves) - 1
    N = Xb.shape[0]
    if n_nodes == 0:
        return dict(waves=1, rows_active=[N], rows_split=[0],
                    compacted=[N < int(N * frac)], scan_pending=[1])
    left, right = np.asarray(tree.left_child), np.asarray(tree.right_child)
    feat, thr = np.asarray(tree.split_feature), np.asarray(tree.threshold_bin)
    wave_of = np.zeros(n_nodes, int)
    rows_at = {0: np.arange(N)}
    smaller_rows = {}                  # node -> raw rows of its smaller child
    for i in range(n_nodes):           # ids ascend in order of application
        rows = rows_at.pop(i)
        go_left = Xb[rows, feat[i]] <= thr[i]
        kids = (rows[go_left], rows[~go_left])
        for child, r in zip((left[i], right[i]), kids):
            if child >= 0:
                rows_at[int(child)] = r
                wave_of[int(child)] = wave_of[i] + 1
        n_real = [int(real[r].sum()) for r in kids]
        smaller_rows[i] = len(kids[0] if n_real[0] <= n_real[1] else kids[1])
        rows_at[("split", i)] = len(rows)
    n_waves = int(wave_of.max()) + 1
    # the split scan of a wave holds the wave's pending leaves (the smaller
    # children of the nodes the wave before split) and their siblings by
    # subtraction; the root has no sibling
    active, split, scanned = [N], [], [1]
    for w in range(n_waves):
        nodes = np.nonzero(wave_of == w)[0]
        split.append(sum(rows_at[("split", int(i))] for i in nodes))
        active.append(sum(smaller_rows[int(i)] for i in nodes))
        scanned.append(2 * len(nodes))
    if int(tree.num_leaves) < L:       # stopped on gain: a wave with no split
        n_waves += 1
        split.append(0)
    else:
        active.pop()                   # budget spent: the loop ends there
        scanned.pop()
    return dict(waves=n_waves, rows_active=active, rows_split=split,
                compacted=[a < int(N * frac) for a in active],
                scan_pending=scanned)


@pytest.mark.parametrize("frac,min_leaf,width,tail_pairs", [
    (0.5, 5, NARROW, 0), (1.0, 5, NARROW, 0), (1e-9, 5, NARROW, 0),
    (0.25, 400, NARROW, 0), (0.0, 5, NARROW, 0), (0.0, 5, WIDE, 0),
    (0.5, 5, NARROW, 1), (0.25, 400, NARROW, 3), (0.0, 5, WIDE, 4)],
    ids=["mixed", "all-compact", "all-stream", "stops-on-gain",
         "auto-narrow", "auto-wide", "mixed-tail-by-1",
         "stops-on-gain-tail-by-3", "auto-wide-tail-by-4"])
def test_counters_equal_numpy_replay(clean_registry, monkeypatch, frac,
                                     min_leaf, width, tail_pairs):
    """``frac`` 0 is the default: the rule of ops/histogram.py sets the
    threshold from the table's width. A wide table streams its root and
    nothing else; a narrow one keeps streaming its early waves.
    ``tail_pairs`` > 0 puts the table on the wide side of
    ``grower.scan_block_pairs``: the wave's tail runs in blocks of that many
    slot pairs and counts the slots it covered; 0 leaves these small tables
    on the narrow side, the static tail, which counts nothing."""
    from lightgbm_tpu import grower
    if tail_pairs:
        monkeypatch.setattr(
            grower, "_SCAN_BLOCK_BYTES",
            tail_pairs * 2 * width["f"] * (width["max_bin"] + 1) * 12)
    params = dict(BASE, tpu_compact_frac=frac, min_data_in_leaf=min_leaf,
                  max_bin=width["max_bin"])
    auto = frac == 0.0
    if auto:
        params.pop("tpu_compact_frac")               # the default, unsaid
    bst = _booster(params, rounds=4, f=width["f"])
    g = bst._gbdt
    if auto:
        frac = g.spec.compact_frac
        assert (frac > 0.5) == (width is WIDE) and 0.0 < frac < 1.0
    L, N = g.spec.num_leaves, int(g.num_data_padded)
    assert g.spec.hist_slots >= L - 1 and N > 3000   # no cap binds; padding
    Xb = np.asarray(g.Xb)
    real = np.asarray(g.pad_mask) > 0
    records = jax.device_get(g._grow_records)
    trees = jax.device_get(g.models)
    seen = {"compact": 0, "stream": 0, "nosplit": 0}
    for rec, (tree,) in zip(records, trees):
        st = jax.tree.map(lambda a: a[0, 0], rec.stats)      # K=1, one device
        want = _replay(tree, Xb, real, L, frac)
        w = int(st.waves)
        assert w == want["waves"]
        assert st.rows_active[:w].tolist() == want["rows_active"]
        assert st.rows_split[:w].tolist() == want["rows_split"]
        assert st.compacted[:w].tolist() == want["compacted"]
        assert st.scan_pending[:w].tolist() == want["scan_pending"]
        if tail_pairs:
            # the loop's own count: whole blocks over the pending leaves
            b = grower.scan_block_pairs(g.spec.hist_slots, g.Xb.shape[1],
                                        g.spec.num_bins_padded)
            assert 1 <= b < g.spec.hist_slots
            pending = [max(1, held // 2) for held in want["scan_pending"]]
            assert st.scan_slots[:w].tolist() == \
                [2 * b * -(-k // b) for k in pending]
        else:
            assert st.scan_slots is None
        assert int(rec.num_leaves[0]) == int(tree.num_leaves)
        seen["compact"] += sum(want["compacted"])
        seen["stream"] += w - sum(want["compacted"])
        seen["nosplit"] += want["rows_split"][-1] == 0
    # the arms this case is there to force did run
    assert seen["stream"] >= 4                       # every root pass streams
    assert (seen["nosplit"] > 0) == (min_leaf == 400)
    if auto:
        # wide: the root's pass and no other; narrow: early waves as well
        assert (seen["stream"] == 4) == (width is WIDE)
        assert seen["compact"] > 0 or width is NARROW
    else:
        assert (seen["compact"] > 0) == (frac > 1e-9)

    bst._ensure_finalized()                          # trees come to the host
    reg = obs.get_registry()
    waves = reg.summary("grow.waves").values()
    assert waves == [float(int(r.stats.waves[0, 0])) for r in records]
    snap = obs.snapshot()["counters"]
    assert snap["rows.routed"] == sum(waves) * N     # measured, not n_trees*N
    touched = reg.summary("grow.hist_rows_touched").values()
    active = reg.summary("grow.hist_rows_active").values()
    chunk = g.spec.chunk_rows
    for rec, t, a in zip(records, touched, active):
        st = jax.tree.map(lambda x: x[0, 0], rec.stats)
        w = int(st.waves)
        by_hand = sum((-(-int(r) // chunk) * chunk) if c else N
                      for r, c in zip(st.rows_active[:w], st.compacted[:w]))
        assert t == by_hand and a == int(st.rows_active[:w].sum()) <= t
    cells = g.Xb.shape[1] * g.spec.num_bins_padded
    assert snap["hist.mxu_flops"] == \
        2 * sum(touched) * cells * g.spec.hist_slots * 5    # hi/lo channels
    assert snap["hist.floor_flops"] == 2 * sum(touched) * cells * 3
    # publishing again publishes nothing again
    bst._ensure_finalized()
    g.publish_telemetry()
    assert reg.summary("grow.waves").count == 4


def test_wave_totals_takes_the_pace_setting_shard():
    """Two devices: per wave the larger shard counts; a wave streams when
    any shard streams it."""
    st = WaveStats(waves=np.array([2, 2]),
                   rows_active=np.array([[100, 10, 0], [100, 30, 0]]),
                   compacted=np.array([[False, True, False],
                                       [False, False, False]]),
                   rows_split=np.array([[100, 40, 0], [100, 70, 0]]),
                   scan_pending=np.array([[1, 2, 0], [1, 2, 0]]))
    t = wave_totals(st, rows_per_device=100, chunk_rows=20, hist_slots=4)
    assert t == {"waves": 2, "stream_passes": 2, "compact_passes": 0,
                 # shard 0 compacted the wave that shard 1 streamed
                 "shard_passes": [(1, 1), (2, 0)],
                 "hist_rows_touched": 200, "hist_chunks": 10,
                 "hist_rows_active": 130,
                 "rows_routed": 200, "rows_split": 170,
                 "scan_slots": 16, "scan_slots_pending": 3,
                 # this program has no one-leaf form and carries no counter
                 "one_leaf_passes": None, "hist_rows_one_leaf": None,
                 "hist_chunks_one_leaf": None}
    one = jax.tree.map(lambda a: a[:1], st)
    t = wave_totals(one, rows_per_device=100, chunk_rows=20, hist_slots=4)
    assert (t["stream_passes"], t["compact_passes"]) == (1, 1)
    assert t["hist_rows_touched"] == 100 + 20          # ceil(10/20) chunks


def test_bagged_root_counts_its_bag_and_compacts(clean_registry):
    """Under a row sample ``n_active`` counts the INCLUDED rows of the
    pending leaves (the histogram's row set; out-of-bag rows route, in no
    slot): a bagged root holds its bag, and compacts wherever the bag's
    share is under the threshold. Until PR 36 it held every row and
    streamed under any threshold."""
    params = dict(BASE, bagging_fraction=0.5, bagging_freq=1,
                  tpu_compact_frac=0.9)
    g = _booster(params, rounds=3)._gbdt
    N = int(g.num_data)
    for rec in jax.device_get(g._grow_records):
        st = jax.tree.map(lambda a: a[0, 0], rec.stats)
        assert int(st.rows_active[0]) == int(rec.sample.rows_in)
        assert 0.4 * N < int(st.rows_active[0]) < 0.6 * N
        assert st.compacted[:int(st.waves)].all()


@pytest.mark.parametrize("learner,batch", [("serial", 1), ("serial", 4),
                                           ("data", 1), ("data", 4)])
def test_default_threshold_grows_the_explicit_values_trees(clean_registry,
                                                           learner, batch):
    """The default resolves to a number once, where the spec is built; the
    same number given explicitly is the same program and the same trees.
    On the wide table that is one streamed pass a tree (serial)."""
    params = dict(BASE, tree_learner=learner, tree_batch=batch,
                  max_bin=WIDE["max_bin"])

    def grow(extra):
        bst = _booster(dict(params, **extra), f=WIDE["f"])
        for _ in range(8 // batch):
            bst._gbdt.train_batch(batch)
        bst._ensure_finalized()
        return bst

    auto = grow({})
    frac = auto._gbdt.spec.compact_frac
    assert 0.5 < frac < 1.0
    streamed = obs.get_registry().summary("grow.stream_passes").values()
    # each shard decides on ITS rows, and a wave counts as streamed when any
    # shard streams it: a 375-row shard can hold the larger part of a leaf
    # whose smaller child is the smaller one over all rows
    assert streamed == [1.0] * 8 if learner == "serial" else (
        len(streamed) == 8 and min(streamed) >= 1.0)
    assert auto.model_to_string() == grow(
        dict(tpu_compact_frac=frac)).model_to_string()


# ---------------------------- (a) the counters' consumers change no tree

@pytest.mark.parametrize("learner,batch", [("serial", 1), ("serial", 4),
                                           ("data", 1), ("data", 4)])
def test_trees_identical_with_and_without_consumers(clean_registry, learner,
                                                    batch):
    """Fetching and publishing the records after every dispatch (what a
    callback that predicts does) leaves the model what an untouched run
    grows, bit for bit; under ``tree_learner=data`` each device counts its
    own shard and the counters come back one row per device."""
    params = dict(BASE, tree_learner=learner, tree_batch=batch,
                  tpu_compact_frac=0.5)

    def grow(consume):
        bst = _booster(params)
        for _ in range(8 // batch):
            bst._gbdt.train_batch(batch)
            if consume:
                bst._ensure_finalized()
                bst._gbdt.publish_telemetry()
        return bst

    quiet, watched = grow(False), grow(True)
    assert obs.get_registry().summary("grow.waves").count == 8
    quiet._ensure_finalized()
    assert quiet.model_to_string() == watched.model_to_string()
    rec = jax.device_get(quiet._gbdt._grow_records[-1])
    n_dev = quiet._gbdt.pctx.num_devices if learner == "data" else 1
    assert rec.stats.rows_active.shape[:2] == (1, n_dev)
    if learner == "data":
        assert n_dev > 1
        rows = quiet._gbdt.num_data_padded // n_dev
        # every shard starts a tree with all of ITS rows pending
        assert (rec.stats.rows_active[0, :, 0] == rows).all()
        assert len(set(rec.stats.waves[0].tolist())) == 1


# ------------------- (d) a profiler session: no new sync, no recompile

def _guarded_loop(g, label, iters=4):
    guard = RecompileGuard(label=label, fail=False)
    guard.register(g._step_fn, "train_step")
    with guard:
        guard.mark_warm()
        for _ in range(iters):
            g.train_one_iter()
        np.asarray(g.score).sum()                  # the one intended sync
    return guard.report()


def test_profiler_session_adds_no_sync_and_no_recompile(clean_registry,
                                                        tmp_path):
    """"Tracing on" is "a profiler session is open": the steady state then
    makes the same host syncs and no recompile, and the program's spans
    are in the profiler's own host plane, nested as the host ran them."""
    g = _booster(BASE, rounds=2)._gbdt
    base = _guarded_loop(g, "session-closed")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced = _guarded_loop(g, "session-open")
    finally:
        jax.profiler.stop_trace()
    assert traced["post_warmup_cache_misses"] == 0
    assert traced["host_syncs"] == base["host_syncs"]
    assert not obs.enabled() and obs.get_tracer().events() == []

    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(obs.PROFILER_PREFIX):
                        spans.setdefault(e.name.split("#")[0], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for name in ("lgbm.tree_batch", "lgbm.iteration", "lgbm.step.prep",
                 "lgbm.step.dispatch", "lgbm.step.post"):
        assert len(spans.get(name, [])) == 4, (name, sorted(spans))
    for (s0, e0), (s1, e1) in zip(sorted(spans["lgbm.tree_batch"]),
                                  sorted(spans["lgbm.step.dispatch"])):
        assert s0 <= s1 and e1 <= e0


# ------------------------------------------------ (e) the span API itself

def test_span_is_the_shared_noop_when_nothing_listens(clean_registry):
    assert not obs.enabled()
    first = obs.span("step.dispatch", site="x")
    assert first is obs.span("anything") is obs.get_tracer().span("t")
    with first as value:
        assert value is None
    assert obs.get_tracer().events() == []


def test_spans_record_the_span_that_caused_them(clean_registry):
    obs.configure(enabled=True)
    with obs.span("outer"):
        with obs.span("inner"):
            with obs.span("innermost"):
                pass
        with obs.span("sibling"):
            pass
    with obs.span("later"):
        pass
    ev = {e["name"]: e for e in obs.get_tracer().events()}
    assert ev["outer"]["parent_id"] is None and ev["later"]["parent_id"] is None
    assert ev["inner"]["parent_id"] == ev["outer"]["span_id"]
    assert ev["innermost"]["parent_id"] == ev["inner"]["span_id"]
    assert ev["sibling"]["parent_id"] == ev["outer"]["span_id"]
    assert len({e["span_id"] for e in ev.values()}) == 5


def test_setup_spans_always_set_their_gauge(clean_registry):
    """Set-up boundaries are timed with the tracer off: the benchmark reads
    them from the registry."""
    # float64 rows: the copy and the round-trip check run for them alone
    # (float32 rows are kept as they are, tests/test_float32_input.py)
    X, y = _data()
    params = dict(BASE, tpu_ingest="device")
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X.astype(np.float64), label=y, params=params))
    bst.update()
    bst._ensure_finalized()
    gauges = obs.snapshot()["gauges"]
    for name in ("setup.dataset_to_float_s", "setup.dataset_lossless_check_s",
                 "setup.dataset_find_bins_s", "setup.ingest_s",
                 "setup.ingest_compile_s", "setup.finalize_fetch_s"):
        assert gauges[name] > 0, name
    assert gauges["setup.ingest_compile_s"] <= gauges["setup.ingest_s"]
    assert obs.get_tracer().events() == []


# ------------------------------------------------ (f) the retrace counter

def test_step_traces_counts_a_forced_retrace(clean_registry):
    obs.configure(enabled=True)
    bst = _booster(BASE, rounds=3)
    g = bst._gbdt
    counters = lambda: obs.snapshot()["counters"]          # noqa: E731
    assert counters()["compile.step_traces"] == g._step_traces == 1
    executables = counters()["compile.step_executables"]
    assert executables == g._step_fn._cache_size() >= 1
    # the same jitted step, an argument of another dtype: jax traces again
    lr = g._shrink_cache[0]
    g._shrink_cache = (lr, g._shrink_cache[1].astype(jax.numpy.bfloat16))
    bst.update()
    assert counters()["compile.step_traces"] == g._step_traces == 2
    assert counters()["compile.step_executables"] == executables + 1
    bst.update()                                           # steady again
    assert counters()["compile.step_traces"] == 2
    assert counters()["compile.step_executables"] == \
        g._step_fn._cache_size() == executables + 1
    traces = [e for e in obs.get_tracer().events()
              if e["name"] == "compile.step_trace"]
    assert [e["args"]["traces"] for e in traces][0] == 1
    assert traces[-1]["args"]["traces"] == 2 and traces[-1]["args"]["retraced"]
    assert traces[0]["args"]["changed"] == {}
    changed = traces[-1]["args"]["changed"]         # [7]: the shrinkage
    assert "float32[]" in changed["[7]"] and "bfloat16[]" in changed["[7]"]
    assert any(v.startswith("float32[1, ") for v in
               traces[-1]["args"]["signature"].values())    # the score


# ------------------------------------------------- the one-leaf form's counters

@pytest.fixture
def one_leaf_form_on(monkeypatch):
    """The one-leaf kernel interpreted: on the CPU the form exists only so."""
    from lightgbm_tpu.ops import pallas_histogram
    monkeypatch.setattr(pallas_histogram, "_INTERPRET", True)


@pytest.mark.parametrize("frac,width", [(0.5, NARROW), (1.0, NARROW),
                                        (0.0, WIDE)],
                         ids=["mixed", "all-compact", "auto-wide"])
def test_one_leaf_waves_are_the_waves_with_one_pending_leaf(
        clean_registry, one_leaf_form_on, frac, width):
    """``WaveStats.one_leaf`` against the replay: true for the root's wave
    and its smaller child's, and later only where the wave before split ONE
    node; such a wave takes its own stream-or-compact threshold
    (``spec.one_leaf_frac``). The published totals against a hand count."""
    params = dict(BASE, max_bin=width["max_bin"])
    if frac:
        params["tpu_compact_frac"] = frac
    bst = _booster(params, rounds=3, f=width["f"])
    g = bst._gbdt
    assert 0 < g.spec.one_leaf_frac <= g.spec.compact_frac
    L, N, chunk = g.spec.num_leaves, int(g.num_data_padded), g.spec.chunk_rows
    Xb, real = np.asarray(g.Xb), np.asarray(g.pad_mask) > 0
    records = jax.device_get(g._grow_records)
    by_hand = []
    for rec, (tree,) in zip(records, jax.device_get(g.models)):
        st = jax.tree.map(lambda a: a[0, 0], rec.stats)
        want = _replay(tree, Xb, real, L, g.spec.compact_frac)
        w = int(st.waves)
        # pending leaves of a wave: the root, then one per node split in the
        # wave before (scan_pending counts them and their siblings)
        one_leaf = [held <= 2 for held in want["scan_pending"]]
        assert w == want["waves"] >= 3 and one_leaf[:2] == [True, True]
        assert not all(one_leaf)
        assert st.one_leaf[:w].tolist() == one_leaf
        compacted = [a < int(N * g.spec.one_leaf_frac) if ol else c
                     for a, c, ol in zip(want["rows_active"],
                                         want["compacted"], one_leaf)]
        assert st.compacted[:w].tolist() == compacted
        touched = [(-(-a // chunk) * chunk) if c else N
                   for a, c in zip(want["rows_active"], compacted)]
        by_hand.append((sum(one_leaf), sum(t for t, ol in zip(touched, one_leaf)
                                           if ol), sum(touched)))
    bst._ensure_finalized()
    reg = obs.get_registry()
    assert reg.summary("grow.one_leaf_passes").values() == \
        [float(b[0]) for b in by_hand]
    assert reg.summary("grow.hist_rows_one_leaf").values() == \
        [float(b[1]) for b in by_hand]
    assert reg.summary("grow.hist_rows_touched").values() == \
        [float(b[2]) for b in by_hand]
    # a chunk of a one-leaf wave folds into the form's own accumulator
    gauges = obs.snapshot()["gauges"]
    acc, acc_one = gauges["hist.acc_bytes"], gauges["hist.one_leaf_acc_bytes"]
    # (smaller than the general one wherever the table is not tiny)
    assert acc_one > 0 and (acc_one < acc or width is NARROW)
    assert reg.summary("grow.hist_acc_bytes").values() == [
        2.0 * ((b[2] - b[1]) // chunk * acc + b[1] // chunk * acc_one)
        for b in by_hand]


def test_one_leaf_is_the_same_on_every_shard(clean_registry, one_leaf_form_on):
    """Under ``tree_learner=data`` the pending leaves are the TREE's, so all
    four shards record the same ``one_leaf`` in every wave, whatever arm each
    took for its own rows."""
    params = dict(BASE, tree_learner="data", num_machines=4)
    g = _booster(params, rounds=2)._gbdt
    assert g.spec.one_leaf_frac > 0
    for rec in jax.device_get(g._grow_records):
        one_leaf = np.asarray(rec.stats.one_leaf)[0]          # [D, W]
        w = int(np.asarray(rec.stats.waves).max())
        assert one_leaf.shape[0] == 4 and w >= 3
        assert (one_leaf == one_leaf[:1]).all()
        assert one_leaf[0, :2].all() and not one_leaf[0, :w].all()


def test_wave_totals_counts_the_one_leaf_waves():
    """Two devices: a one-leaf wave's touched rows are the pace-setting
    shard's, streamed or compacted; the others' are left out."""
    st = WaveStats(waves=np.array([3, 3]),
                   rows_active=np.array([[100, 30, 50], [100, 45, 50]]),
                   compacted=np.array([[False, True, True],
                                       [False, True, True]]),
                   rows_split=np.array([[100, 40, 0], [100, 70, 0]]),
                   scan_pending=np.array([[1, 2, 4], [1, 2, 4]]),
                   one_leaf=np.array([[True, True, False],
                                      [True, True, False]]))
    t = wave_totals(st, rows_per_device=100, chunk_rows=20, hist_slots=4)
    assert t["one_leaf_passes"] == 2
    assert t["hist_rows_one_leaf"] == 100 + 60            # ceil(45/20) chunks
    assert t["hist_chunks_one_leaf"] == 8
    assert t["hist_rows_touched"] == 100 + 60 + 60
    assert t["hist_chunks"] == 11


def test_a_program_without_the_form_publishes_no_one_leaf_count(clean_registry):
    """On the CPU the Mosaic kernel does not exist: the spec says so, the
    loop carries no ``one_leaf`` and nothing is published."""
    bst = _booster(BASE, rounds=2)
    g = bst._gbdt
    assert g.spec.one_leaf_frac == 0
    assert all(r.stats.one_leaf is None for r in g._grow_records)
    bst._ensure_finalized()
    reg = obs.get_registry()
    assert reg.summary("grow.one_leaf_passes").count == 0
    assert reg.summary("grow.hist_rows_one_leaf").count == 0
    assert reg.summary("grow.waves").count == 2
