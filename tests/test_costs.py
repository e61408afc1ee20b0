"""Cost & memory introspection (observability/costs.py, memory.py,
ledger.py; docs/Observability.md "Cost & memory introspection"):

- golden cost-report pins for the fused train step and the histogram
  kernel (tolerance-banded against tests/fixtures/cost_golden.json),
- the cost_analysis()-returns-None graceful-fallback path,
- HBM pre-flight estimate vs compiled memory_analysis() agreement on two
  shape classes,
- per-collective comm byte estimates,
- the perf regression ledger: build, best-known, injected-regression
  compare (API and `bench.py --compare` CLI), drift check,
- snapshot/dump-snapshot integration.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.observability import costs, ledger
from lightgbm_tpu.observability.memory import (device_memory,
                                               estimate_wave_residency,
                                               hbm_preflight, log_budget)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
GOLDEN = json.load(open(os.path.join(HERE, "fixtures", "cost_golden.json")))


@pytest.fixture
def cost_capture():
    """Fresh observability singletons with cost capture forced on."""
    obs.reset_for_tests()
    costs.configure(enabled=True)
    yield costs
    obs.reset_for_tests()


def _data(n=2048, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.65).astype(np.float32)
    return X, y


PARAMS = dict(objective="binary", num_leaves=15, max_bin=31,
              min_data_in_leaf=5, verbose=-1, metric="none",
              tpu_hist_kernel="xla", tree_batch=2)


def _fused_booster(n=2048, f=8, params=None):
    X, y = _data(n, f)
    p = dict(PARAMS, **(params or {}))
    ds = lgb.Dataset(X, label=y, params=p)
    return lgb.Booster(params=p, train_set=ds)


# ------------------------------------------------------------- cost capture

def test_fused_step_report_matches_golden(cost_capture):
    """The exact golden-pin shape: capture at first dispatch, fields
    populated, FLOPs/bytes inside the committed tolerance band."""
    bst = _fused_booster()
    bst._gbdt.train_batch(2)
    rep = costs.report("train_step.k2")
    assert rep is not None and not rep.get("error")
    assert rep["tree_batch"] == 2 and rep["kernel"] == "xla"
    for field in ("flops", "bytes_accessed", "argument_bytes", "temp_bytes",
                  "peak_hbm_bytes"):
        assert rep[field] is not None and rep[field] > 0, (field, rep)
    bad = costs.drift(rep, GOLDEN["test_train_step_k2"])
    assert bad == {}, f"fused-step cost drifted from golden: {bad}"


def test_capture_happens_once_and_publishes(cost_capture):
    bst = _fused_booster()
    g = bst._gbdt
    for _ in range(3):
        g.train_batch(2)
    snap = obs.snapshot()
    assert "cost_reports" in snap and "train_step.k2" in snap["cost_reports"]
    assert snap["gauges"]["cost.train_step.k2.flops"] > 0
    # once-only per executable: the site maps to THIS booster's fused step
    # (a strong reference — id() reuse after GC cannot skip a new booster)
    assert costs._captured["train_step.k2"][0] is g._batch_step_fns[2]


def test_new_booster_recaptures_its_own_shape(cost_capture):
    """A different executable at a known site replaces the report — a
    second booster with different dims must not inherit stale numbers."""
    _fused_booster(2048, 8)._gbdt.train_batch(2)
    first = costs.report("train_step.k2")
    _fused_booster(4096, 12)._gbdt.train_batch(2)
    second = costs.report("train_step.k2")
    assert second["rows"] == 4096 and second["features"] >= 12
    assert second["flops"] > first["flops"]


def test_capture_disabled_is_noop():
    obs.reset_for_tests()
    try:
        assert not costs.enabled()
        bst = _fused_booster()
        bst._gbdt.train_batch(2)
        assert costs.reports() == {}
    finally:
        obs.reset_for_tests()


def test_histogram_kernel_report_matches_golden(cost_capture):
    from lightgbm_tpu.ops.histogram import histogram_cost_report
    rep = histogram_cost_report(4096, 8, 32, 14, 1024)
    assert not rep.get("error"), rep
    assert rep["flops"] and rep["bytes_accessed"]
    bad = costs.drift(rep, GOLDEN["test_histogram_stream"])
    assert bad == {}, f"histogram kernel cost drifted from golden: {bad}"
    assert costs.report("histogram.stream.s14") is not None


def test_predict_dispatch_capture(cost_capture):
    """The stacked-forest predict path captures its walk's report."""
    X, y = _data()
    p = dict(PARAMS, tree_batch=1)
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)
    from lightgbm_tpu.ops.predict import forest_predict_raw
    out = forest_predict_raw(bst.trees, X[:256].astype(np.float64),
                             bst.num_total_features)
    assert out.shape == (256,)
    rep = costs.report("predict.forest_walk")
    assert rep is not None and rep["trees"] == 3
    assert rep["flops"] is not None
    # _forest_walk is one shared jit: a different forest/batch shape must
    # re-capture (fingerprint), not serve the first model's numbers
    X2, y2 = _data(seed=1)
    bst2 = lgb.train(p, lgb.Dataset(X2, label=y2, params=p),
                     num_boost_round=5)
    forest_predict_raw(bst2.trees, _data()[0][:64].astype(np.float64),
                       bst2.num_total_features)
    rep2 = costs.report("predict.forest_walk")
    assert rep2["trees"] == 5 and rep2["rows"] == 64


# ------------------------------------------------------- graceful fallback

class _NoneAnalyses:
    def cost_analysis(self):
        return None

    def memory_analysis(self):
        return None


class _RaisingAnalyses:
    def cost_analysis(self):
        raise RuntimeError("Unimplemented on this backend")

    def memory_analysis(self):
        raise RuntimeError("Unimplemented on this backend")


@pytest.mark.parametrize("compiled", [_NoneAnalyses(), _RaisingAnalyses()])
def test_cost_analysis_none_fallback(compiled):
    """A backend returning None (or raising) from either analysis yields a
    report with None fields — never an exception."""
    rep = costs.report_from_compiled(compiled, "site.x", dims={"rows": 4})
    assert rep["site"] == "site.x" and rep["rows"] == 4
    for field in ("flops", "bytes_accessed", "argument_bytes", "temp_bytes",
                  "peak_hbm_bytes"):
        assert rep[field] is None


def test_capture_failure_records_error(cost_capture):
    class NotJitted:
        def lower(self, *a, **kw):
            raise TypeError("no lowering for you")

    rep = costs.capture_jit("broken.site", NotJitted(), (1, 2))
    assert "no lowering for you" in rep["error"]
    assert costs.report("broken.site")["error"]  # recorded, not raised


def test_drift_bands():
    rep = {"flops": 100.0, "bytes_accessed": None}
    assert costs.drift(rep, {"flops": 100.0}) == {}
    assert costs.drift(rep, {"flops": 120.0}) == {}          # within 35%
    assert "flops" in costs.drift(rep, {"flops": 300.0})
    # losing the measurement against a numeric golden IS drift
    assert "bytes_accessed" in costs.drift(rep, {"bytes_accessed": 50.0})
    # tighter band via the golden itself
    assert "flops" in costs.drift(rep, {"flops": 120.0, "rel_tol": 0.1})


# ------------------------------------------------------------ HBM pre-flight

@pytest.mark.parametrize("shape", [
    dict(n=2048, f=8, params={}),
    dict(n=6144, f=20, params=dict(num_leaves=31, max_bin=63)),
])
def test_preflight_agrees_with_compiled_memory_analysis(cost_capture, shape):
    """The analytic residency estimate must sit in the same ballpark as the
    compiled step's memory_analysis() (argument + temp bytes), once the
    one-hot operand of a chunk is added to it: the CPU backend this test
    compiles for allocates it whole, in f32, where the TPU the model is for
    fuses it into the matmul and allocates none (PERF.md, PR 30). The band
    is wide, but a broken model (10x off) fails."""
    bst = _fused_booster(shape["n"], shape["f"], shape["params"])
    g = bst._gbdt
    g.train_batch(2)
    rep = costs.report("train_step.k2")
    assert rep and rep["argument_bytes"] and rep["temp_bytes"]
    est = hbm_preflight(g)
    compiled_total = rep["argument_bytes"] + rep["temp_bytes"]
    d = est["dims"]
    onehot_on_cpu = d["chunk_rows"] * d["hist_cols"] * d["hist_bins"] * 4
    ratio = (est["total_bytes"] + onehot_on_cpu) / compiled_total
    assert 0.2 <= ratio <= 2.5, (ratio, est, rep)


def test_preflight_components_and_gauges(cost_capture):
    bst = _fused_booster()
    est = hbm_preflight(bst._gbdt)
    comp = est["components"]
    for key in ("codes", "scores", "gradients", "partition", "packed",
                "hist_cache", "wave_temps"):
        assert comp[key] > 0, (key, comp)
    assert est["total_bytes"] == sum(comp.values())
    snap = obs.snapshot()
    assert snap["gauges"]["memory.preflight.total_bytes"] == \
        est["total_bytes"]
    # dims are recorded so a reader can reproduce the estimate
    assert est["dims"]["rows"] == bst._gbdt.num_data_padded


def test_estimate_scales_linearly_in_rows():
    base = dict(cols=28, code_itemsize=1, num_models=1, num_leaves=255,
                hist_cols=28, hist_bins=256, cache_cols=28, cache_bins=256,
                num_bins_padded=256, slots=25, chunk_rows=32768, channels=5,
                channel_bytes=2, packed_row_bytes=38)
    small = estimate_wave_residency(rows=10_500_000, **base)
    big = estimate_wave_residency(rows=105_000_000, **base)
    assert big["total_bytes"] > 5 * small["total_bytes"]
    # O(N) components scale 10x; resident compute temps do not
    assert big["components"]["codes"] == 10 * small["components"]["codes"]
    assert big["components"]["wave_temps"] == \
        small["components"]["wave_temps"]


def test_budget_line_warns_over_capacity():
    est = {"components": {"codes": 2 << 30}, "total_bytes": 2 << 30}
    assert log_budget(est, {"capacity_bytes": 1 << 30,
                            "platform": "test"}) is False
    assert log_budget(est, {"capacity_bytes": 4 << 30,
                            "platform": "test"}) is True
    assert log_budget(est, {}) is True          # unknown capacity: no warn


def test_device_memory_backend_fallback():
    import jax
    # with no backend yet initialized the probe must return {} rather than
    # force an init; jax.devices() then initializes it for real
    dm_or_empty = device_memory()
    assert dm_or_empty == {} or "platform" in dm_or_empty
    jax.devices()
    dm = device_memory()
    # CPU backend: stats may be empty, but the normalized keys exist and
    # nothing raises
    assert "platform" in dm
    assert "peak_bytes" in dm and "capacity_bytes" in dm


# --------------------------------------------------------------- comm bytes

def test_collective_bytes_estimates():
    from lightgbm_tpu.parallel.comm import (DataParallelComm,
                                            FeatureParallelComm, SerialComm,
                                            VotingParallelComm)
    S, B = 25, 256
    assert SerialComm(28).collective_bytes(S, B) == {}
    dp = DataParallelComm("shard", 8, 32).collective_bytes(S, B)
    # the reduce-scatter covers the S freshly-built histograms; the
    # candidate all-gather carries the 2S slot+sibling scan rows (the
    # round-6 measured-HLO validation pinned the 2x)
    assert dp["psum_scatter_hist"] == S * 32 * B * 3 * 4
    assert dp["allgather_splits"] == 8 * 2 * S * (4 * 4 + 2 * 4 + 2 + B)
    fp = FeatureParallelComm("shard", 8, 32).collective_bytes(S, B)
    assert set(fp) == {"allgather_splits"}
    vp = VotingParallelComm("shard", 8, 512, top_k=20).collective_bytes(S, B)
    # the PV-Tree trade: selected-feature reduce << full-width reduce
    full = 2 * S * 512 * B * 3 * 4
    assert vp["psum_selected_hist"] == 2 * S * 40 * B * 3 * 4 < full


def test_booster_publishes_comm_gauges(cost_capture):
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under "
                    "--xla_force_host_platform_device_count)")
    X, y = _data()
    p = dict(PARAMS, tree_learner="data", num_machines=2, tree_batch=1)
    ds = lgb.Dataset(X, label=y, params=p)
    lgb.Booster(params=p, train_set=ds)
    gauges = obs.snapshot()["gauges"]
    assert any(k.startswith("comm.bytes_per_wave.") for k in gauges), gauges


# ------------------------------------------------------------------- ledger

_HEADLINE_KEY = ("platform=tpu|rows=10500000|kernel=xla|n_devices=None"
                 "|residency=None|serve=None|serve_chaos=None|chaos_dist=None"
                 "|bundle=None|linear=None|ingest=None")


def _synthetic_history(root):
    """A history directory in every shape load_history must read: a driver
    wrapper with no parsed payload, a bare errored payload, a wrapper
    holding a clean payload, and a bare clean payload that is the best."""
    head = {"metric": "higgs_train_throughput", "unit": "Mrow-tree/s",
            "platform": "tpu", "rows": 10_500_000, "kernel": "xla"}
    docs = {
        "BENCH_r01.json": {"n": 1, "cmd": "python bench.py", "rc": 1,
                           "tail": "backend init failed", "parsed": None},
        "BENCH_r02.json": dict(head, value=0.0, error="no chip"),
        "BENCH_r03.json": {"n": 3, "cmd": "python bench.py", "rc": 0,
                           "tail": "", "parsed": dict(head, value=4.0)},
        "BENCH_r04.json": dict(head, value=6.0, auc=0.9,
                               recompiles_post_warmup=0),
        "STREAM_r05.json": dict(head, value=2.0, residency="stream"),
    }
    for name, doc in docs.items():
        (root / name).write_text(json.dumps(doc))
    return str(root)


def test_ledger_builds_from_history(tmp_path):
    root = _synthetic_history(tmp_path)
    entries = ledger.load_history(root)
    assert [e["source"] for e in entries] == [
        "BENCH_r01.json", "BENCH_r02.json", "BENCH_r03.json",
        "BENCH_r04.json", "STREAM_r05.json"]
    assert entries[0]["error"] == "unparseable history file"
    doc = ledger.build_ledger(root)
    assert doc["best"][_HEADLINE_KEY]["value"] == 6.0
    assert doc["best"][_HEADLINE_KEY]["source"] == "BENCH_r04.json"
    # the written ledger matches the history (no drift) — the same
    # invariant `make bench-diff` enforces on the repo's own
    ledger.write_ledger(root)
    assert ledger.check_ledger(root)
    # ... and the repo's committed ledger matches ITS history files
    assert ledger.check_ledger(REPO)


def test_compare_flags_injected_throughput_regression(tmp_path):
    entries = ledger.load_history(_synthetic_history(tmp_path))
    bad = {"metric": "higgs_train_throughput", "value": 3.0,
           "unit": "Mrow-tree/s", "platform": "tpu", "rows": 10_500_000,
           "kernel": "xla"}
    problems, _ = ledger.compare(bad, entries)
    assert any("throughput regression" in p for p in problems)
    ok = dict(bad, value=5.8)
    problems, notes = ledger.compare(ok, entries)
    assert problems == [] and any("throughput ok" in n for n in notes)


def test_compare_flags_recompile_and_cost_drift():
    entries = [ledger.normalize_bench(
        {"value": 6.0, "platform": "tpu", "rows": 100,
         "recompiles_post_warmup": 0, "hbm_peak_gb": 2.0,
         "phase_timings": {"headline": {"host_syncs": 1}},
         "telemetry": {"cost_reports": {
             "train_step.k4": {"flops": 1e9, "bytes_accessed": 1e8}}}},
        "BENCH_r90.json", 90)]
    cand = {"value": 6.0, "platform": "tpu", "rows": 100,
            "recompiles_post_warmup": 2, "hbm_peak_gb": 3.0,
            "phase_timings": {"headline": {"host_syncs": 4}},
            "telemetry": {"cost_reports": {
                "train_step.k4": {"flops": 2.5e9, "bytes_accessed": 1e8}}}}
    problems, _ = ledger.compare(cand, entries)
    text = "\n".join(problems)
    assert "recompile regression" in text
    assert "host-sync regression" in text
    assert "peak-HBM regression" in text
    assert "cost drift" in text and "train_step.k4.flops" in text


def test_cost_drift_lost_measurement_is_drift():
    """Same semantics as the golden pin (ONE drift implementation): a
    candidate that stopped reporting a recorded cost field fails the gate."""
    entries = [ledger.normalize_bench(
        {"value": 6.0, "platform": "tpu", "rows": 100,
         "telemetry": {"cost_reports": {
             "train_step.k4": {"flops": 1e9, "bytes_accessed": 1e8}}}},
        "BENCH_r90.json", 90)]
    cand = {"value": 6.0, "platform": "tpu", "rows": 100,
            "telemetry": {"cost_reports": {
                "train_step.k4": {"bytes_accessed": 1e8, "flops": None}}}}
    problems, _ = ledger.compare(cand, entries)
    assert any("train_step.k4.flops" in p and "None" in p for p in problems)


def test_cost_capture_scoped_to_the_run():
    """tpu_cost_analysis=true must not leak capture into later fits."""
    obs.reset_for_tests()
    try:
        X, y = _data()
        p = dict(PARAMS, tpu_cost_analysis=True)
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
        assert costs.report("train_step.k2") is not None
        assert not costs.enabled()      # restored after the run
    finally:
        obs.reset_for_tests()


def test_compare_rejects_unclean_candidate(tmp_path):
    problems, _ = ledger.compare(
        {"value": 0.0, "error": "no chip"},
        ledger.load_history(_synthetic_history(tmp_path)))
    assert any("no clean measurement" in p for p in problems)


def test_quick_prebank_not_judged_against_headline(tmp_path):
    entries = ledger.load_history(_synthetic_history(tmp_path))
    quick = {"value": 4.0, "platform": "tpu", "rows": 2_100_000}
    problems, notes = ledger.compare(quick, entries)
    assert problems == []
    assert any("no comparable history" in n for n in notes)


@pytest.mark.slow
def test_bench_compare_exit_codes(tmp_path, monkeypatch, capsys):
    """bench.py --compare against a synthetic history directory (the mode
    resolves its history next to bench.py's own path)."""
    sys.path.insert(0, REPO)
    import bench
    root = _synthetic_history(tmp_path)
    monkeypatch.setattr(bench, "__file__", os.path.join(root, "bench.py"))
    bad = tmp_path / "regressed.json"
    bad.write_text(json.dumps(
        {"metric": "higgs_train_throughput", "value": 3.0,
         "platform": "tpu", "rows": 10_500_000, "kernel": "xla"}))
    assert bench.run_compare(["--compare", str(bad)]) == 2
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is False and out["problems"]
    # the newest history file judged against the earlier ones: clean
    assert bench.run_compare(["--compare"]) == 0
    capsys.readouterr()
    # ... and the repo's own (headline-less) history judges clean too
    monkeypatch.setattr(bench, "__file__", os.path.join(REPO, "bench.py"))
    assert bench.run_compare(["--compare"]) == 0


def test_ledger_check_detects_drift(tmp_path):
    src = {"metric": "higgs_train_throughput", "value": 5.0,
           "platform": "tpu", "rows": 100}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(src))
    ledger.write_ledger(str(tmp_path))
    assert ledger.check_ledger(str(tmp_path))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(dict(src, value=6.0)))
    assert not ledger.check_ledger(str(tmp_path))   # history moved on
    ledger.write_ledger(str(tmp_path))
    assert ledger.check_ledger(str(tmp_path))


def test_ledger_wrapper_and_flat_payloads(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 1, "parsed": None}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "rc": 0, "parsed": {"metric": "m", "value": 1.5,
                                     "platform": "tpu"}}))
    entries = ledger.load_history(str(tmp_path))
    assert entries[0]["error"] and entries[0]["value"] is None
    assert entries[1]["value"] == 1.5


# ------------------------------------------------------- snapshot plumbing

def test_train_end_snapshot_dump(tmp_path):
    obs.reset_for_tests()
    try:
        X, y = _data()
        out = tmp_path / "snap.json"
        p = dict(PARAMS, dump_snapshot=str(out), tpu_cost_analysis=True)
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
        snap = json.load(open(out))
        assert snap["counters"]["trees.trained"] == 2
        assert "train_step.k2" in snap["cost_reports"]
        assert snap["gauges"]["memory.preflight.total_bytes"] > 0
    finally:
        obs.reset_for_tests()


def test_telemetry_dir_auto_snapshot(tmp_path):
    obs.reset_for_tests()
    try:
        X, y = _data()
        p = dict(PARAMS, telemetry_dir=str(tmp_path))
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
        snaps = [f for f in os.listdir(tmp_path)
                 if f.startswith("snapshot_") and f.endswith(".json")]
        assert snaps, os.listdir(tmp_path)
        snap = json.load(open(tmp_path / snaps[0]))
        assert "counters" in snap
    finally:
        obs.reset_for_tests()


def test_cli_bare_dump_snapshot_flag():
    from lightgbm_tpu.cli import parse_args
    params = parse_args(["train", "--dump-snapshot"])
    assert params["dump_snapshot"] == "observability_snapshot.json"
    params = parse_args(["--dump-snapshot=/tmp/x.json"])
    assert params["dump_snapshot"] == "/tmp/x.json"


def test_perfetto_metadata_carries_cost_reports(tmp_path, cost_capture):
    obs.configure(telemetry_dir=str(tmp_path))
    bst = _fused_booster()
    bst._gbdt.train_batch(2)
    trace = obs.flush()
    doc = json.load(open(trace))
    assert "train_step.k2" in doc["otherData"]["cost_reports"]
