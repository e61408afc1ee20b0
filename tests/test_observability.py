"""Unified training telemetry (lightgbm_tpu/observability/;
docs/Observability.md): metrics registry, span tracer, exporters, the
jax.profiler window, and the end-to-end engine.train wiring (spans nested
train -> tree_batch -> step.dispatch with parent ids, counters for kernel
choice / trees / rows; the wave loop's own counters have
tests/test_wave_counters.py)."""
import json
import logging
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.observability.export import read_jsonl, write_chrome_trace
from lightgbm_tpu.observability.metrics import MetricsRegistry
from lightgbm_tpu.observability.phases import PhaseBreakdown
from lightgbm_tpu.observability.profiler import (ProfileWindow,
                                                 parse_profile_iters)
from lightgbm_tpu.observability.tracer import SpanTracer


@pytest.fixture
def telemetry(tmp_path):
    """Fresh process-wide singletons pointed at a temp dir; reset after."""
    obs.reset_for_tests()
    obs.configure(telemetry_dir=str(tmp_path))
    yield obs
    obs.reset_for_tests()


@pytest.fixture
def clean_registry():
    obs.reset_for_tests()
    yield obs
    obs.reset_for_tests()


def _data(n=400, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.65).astype(np.float32)
    return X, y


PARAMS = dict(objective="binary", num_leaves=7, max_bin=15,
              min_data_in_leaf=5, verbose=-1, metric="none")


def _padded(n, params=PARAMS):
    """Rows as the device holds them (padded to the histogram chunk): what
    the routing pass moves every wave."""
    X, y = _data(n)
    bst = lgb.Booster(params=dict(params),
                      train_set=lgb.Dataset(X, label=y, params=params))
    return int(bst._gbdt.num_data_padded)


# ------------------------------------------------------------------ registry

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("g").set(3.5)
    for v in (1, 2, 3):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 3.5
    assert snap["histograms"]["h"] == {"count": 3, "sum": 6.0, "min": 1.0,
                                       "max": 3.0, "mean": 2.0}
    json.dumps(snap)                      # serving API must serialize as-is
    reg.reset()
    assert reg.snapshot()["counters"] == {}


# -------------------------------------------------------------------- tracer

def test_tracer_disabled_is_a_noop():
    t = SpanTracer()
    with t.span("a", k=1):
        pass
    t.event("e")
    assert t.events() == []


def test_tracer_spans_nest_by_containment():
    t = SpanTracer()
    t.enabled = True
    with t.span("outer"):
        with t.span("inner", k=2):
            pass
    inner = next(e for e in t.events() if e["name"] == "inner")
    outer = next(e for e in t.events() if e["name"] == "outer")
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert inner["args"]["k"] == 2


def test_tracer_bounded_events():
    t = SpanTracer(max_events=3)
    t.enabled = True
    for i in range(5):
        t.event("e", i=i)
    assert len(t.events()) == 3 and t.dropped == 2


# ----------------------------------------------------------------- exporters

def test_chrome_trace_write_is_valid_and_atomic(tmp_path):
    t = SpanTracer()
    t.enabled = True
    with t.span("a"):
        pass
    path = write_chrome_trace(t.events(), str(tmp_path / "trace.json"))
    with open(path) as fh:
        doc = json.load(fh)
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_flush_appends_jsonl_incrementally(telemetry):
    with obs.span("s"):
        pass
    obs.inc("c")
    trace = obs.flush()
    assert os.path.exists(trace)
    recs = read_jsonl(obs.jsonl_path())
    assert any(r.get("type") == "span" and r["name"] == "s" for r in recs)
    assert [r for r in recs
            if r.get("type") == "counters"][-1]["counters"]["c"] == 1
    obs.flush()                         # no new events -> no duplicate spans
    recs2 = read_jsonl(obs.jsonl_path())
    assert len([r for r in recs2 if r.get("type") == "span"]) == 1
    assert len([r for r in recs2 if r.get("type") == "counters"]) == 2


# ------------------------------------------------------------ PhaseBreakdown

def test_phase_breakdown_schema_and_registry(clean_registry):
    pb = PhaseBreakdown("unit")
    with pb.compile_window():
        pass
    with pb.steady_window(iters=4):
        pass
    pb.attach_guard({"host_syncs": 1, "post_warmup_cache_misses": 0})
    d = pb.to_dict()
    # byte-compatible field set (BENCH_r* trajectory scripts parse this)
    assert set(d) == {"compile_s", "steady_s", "steady_iters",
                      "steady_s_per_iter", "host_syncs",
                      "post_warmup_cache_misses"}
    assert d["steady_iters"] == 4 and d["post_warmup_cache_misses"] == 0
    gauges = obs.get_registry().snapshot()["gauges"]
    assert gauges["phase.unit.steady_iters"] == 4


def test_utils_timer_is_gone_and_its_two_survivors_have_one_home():
    """``utils/timer.py`` went with ``TIMERS``: ``PhaseBreakdown`` is
    observability's (bench.py imports it from there), ``maybe_xla_trace``
    sits beside ``ProfileWindow``."""
    import importlib
    with pytest.raises(ImportError):
        importlib.import_module("lightgbm_tpu.utils.timer")
    assert obs.PhaseBreakdown is PhaseBreakdown
    from lightgbm_tpu.observability.profiler import maybe_xla_trace
    with maybe_xla_trace(""):        # no directory: no trace, no jax
        pass


def test_recompile_guard_publishes_to_registry(clean_registry):
    from lightgbm_tpu.analysis.guards import RecompileGuard
    g = RecompileGuard(label="unit", fail=False)
    with g:
        g.mark_warm()
    snap = obs.get_registry().snapshot()
    assert snap["counters"]["guard.windows"] == 1
    assert "recompiles.post_warmup" not in snap["counters"]   # zero = absent


# ------------------------------------------------------------------ profiler

def test_parse_profile_iters():
    assert parse_profile_iters("") is None
    assert parse_profile_iters("2:5") == (2, 5)
    for bad in ("5", "a:b", "3:3", "-1:2", "1:2:3"):
        with pytest.raises(ValueError):
            parse_profile_iters(bad)


def test_config_validates_profile_iters():
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError, match="tpu_profile_iters"):
        lgb.Config.from_params({"tpu_profile_iters": "7"})


def test_profile_window_needs_an_output_dir():
    assert not ProfileWindow("2:4", "").enabled


def test_profile_window_ticks(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    pw = ProfileWindow("2:4", str(tmp_path))
    for it in range(6):
        pw.before_step(it)
        pw.after_step(it + 1)
    pw.close()
    assert calls == [("start", str(tmp_path)), ("stop",)]


def test_profile_window_inside_one_fused_batch(monkeypatch, tmp_path):
    """A window contained entirely within one fused batch must capture
    that batch (overlap semantics), not be silently skipped."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    pw = ProfileWindow("2:6", str(tmp_path))
    pw.before_step(0, batch=8)          # [0,8) overlaps [2,6)
    pw.after_step(8)
    pw.close()
    assert calls == ["start", "stop"]
    # and a window starting mid-batch opens at the overlapping batch
    calls.clear()
    pw2 = ProfileWindow("3:20", str(tmp_path))
    pw2.before_step(0, batch=8)
    pw2.after_step(8)
    pw2.before_step(8, batch=8)
    pw2.after_step(16)
    pw2.close()
    assert calls == ["start", "stop"]   # started at batch 0, closed at exit


def test_profile_window_resumed_past_window(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    pw = ProfileWindow("2:4", str(tmp_path))
    for it in range(10, 12):            # resume landed past the window
        pw.before_step(it)
        pw.after_step(it + 1)
    pw.close()
    assert calls == []


def test_train_profile_window_batch_aligned(monkeypatch, tmp_path,
                                            clean_registry):
    """tpu_profile_iters under tree_batch: the window opens at the first
    overlapping batch and closes at the first boundary at-or-past stop —
    exactly one start/stop pair, never a mid-batch split."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, *a, **k: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    X, y = _data()
    p = dict(PARAMS, tree_batch=2, tpu_profile_iters="3:5",
             tpu_profile_dir=str(tmp_path / "prof"))
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=8)
    assert calls == [("start", str(tmp_path / "prof")), ("stop",)]


# ------------------------------------------------------------- end-to-end

def _contains(outer, inner):
    return (outer["tid"] == inner["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner.get("dur", 0)
            <= outer["ts"] + outer["dur"] + 1)


def test_train_emits_nested_spans_and_counters(telemetry):
    X, y = _data()
    params = dict(PARAMS, tree_batch=2)
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=6)
    with open(obs.trace_path()) as fh:
        events = json.load(fh)["traceEvents"]
    trains = [e for e in events if e["name"] == "train"]
    batches = [e for e in events if e["name"] == "tree_batch"]
    dispatches = [e for e in events if e["name"] == "step.dispatch"]
    assert len(trains) == 1 and len(batches) == 3 and len(dispatches) == 3
    assert all(_contains(trains[0], b) for b in batches)
    # every span is a measured host boundary: nothing derived, and each
    # names the span that caused it
    assert not [e for e in events if e["name"] in ("iteration", "wave")]
    assert not [e for e in events if (e.get("args") or {}).get("derived")]
    assert all(b["parent_id"] == trains[0]["span_id"] for b in batches)
    for d in dispatches:
        batch = next(b for b in batches if b["span_id"] == d["parent_id"])
        assert _contains(batch, d)

    snap = obs.snapshot()
    assert snap["counters"]["trees.trained"] == 6
    waves = obs.get_registry().summary("grow.waves").values()
    assert len(waves) == 6 and min(waves) >= 1
    assert snap["counters"]["rows.routed"] == sum(waves) * _padded(400)
    assert snap["counters"]["booster.kernel.xla"] == 1
    assert snap["gauges"]["booster.tree_batch"] == 2
    assert snap["histograms"]["tree.leaves"]["count"] == 6
    # JSONL stream carries the same counters next to the events
    recs = read_jsonl(obs.jsonl_path())
    counters = [r for r in recs if r.get("type") == "counters"][-1]
    assert counters["counters"]["trees.trained"] == 6


@pytest.mark.slow
def test_eval_and_checkpoint_spans(telemetry, tmp_path):
    X, y = _data()
    params = dict(PARAMS, metric="binary_logloss",
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_interval=2)
    ds = lgb.Dataset(X, label=y, params=params)
    lgb.train(params, ds, num_boost_round=4,
              valid_sets=[lgb.Dataset(X[:100], label=y[:100], reference=ds)],
              verbose_eval=False)
    names = {e["name"] for e in obs.get_tracer().events()}
    assert "eval" in names and "checkpoint" in names
    assert obs.snapshot()["counters"]["checkpoint.writes"] >= 1


def test_telemetry_dir_param_configures(clean_registry, tmp_path):
    X, y = _data()
    p = dict(PARAMS, telemetry_dir=str(tmp_path / "tel"))
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
    assert obs.enabled() and obs.telemetry_dir() == str(tmp_path / "tel")
    assert os.path.exists(obs.trace_path())


def test_env_var_configures(clean_registry, tmp_path, monkeypatch):
    monkeypatch.setenv(obs.ENV_TELEMETRY_DIR, str(tmp_path / "envtel"))
    X, y = _data()
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y, params=PARAMS),
              num_boost_round=2)
    assert obs.telemetry_dir() == str(tmp_path / "envtel")
    assert os.path.exists(obs.trace_path())


def test_registry_live_without_telemetry_dir(clean_registry):
    """The serving snapshot works with span recording off (the always-on
    leg of the contract) — and no trace/jsonl files are implied."""
    X, y = _data()
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y, params=PARAMS),
              num_boost_round=3)
    assert not obs.enabled()
    assert obs.trace_path() is None
    snap = obs.snapshot()
    assert snap["counters"]["trees.trained"] == 3
    waves = obs.get_registry().summary("grow.waves").values()
    assert len(waves) == 3
    assert snap["counters"]["rows.routed"] == sum(waves) * _padded(400)
    assert snap["spans_recorded"] == 0       # tracer stayed silent


def test_resume_counts_only_new_iterations(clean_registry, tmp_path):
    """A checkpoint-resumed run must not re-count restored iterations into
    the monotonic trees.trained / rows.routed counters."""
    X, y = _data()
    params = dict(PARAMS, checkpoint_dir=str(tmp_path / "ck"),
                  checkpoint_interval=2)
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=4)
    assert obs.snapshot()["counters"]["trees.trained"] == 4
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=8, resume_from="auto")
    snap = obs.snapshot()["counters"]
    assert snap["trees.trained"] == 8            # 4 first run + 4 NEW
    waves = obs.get_registry().summary("grow.waves").values()
    assert len(waves) == 8                       # restored ones not again
    assert snap["rows.routed"] == sum(waves) * _padded(400)


def test_flush_on_failed_training(telemetry):
    """nan_policy=raise aborts the run — the finally-path flush must still
    leave a readable trace + the nan counters behind."""
    from lightgbm_tpu.robustness.chaos import nan_gradient_fobj
    from lightgbm_tpu.robustness.numeric import NonFiniteError
    X, y = _data()
    params = dict(objective="none", verbose=-1, metric="none",
                  boost_from_average=False, nan_policy="raise",
                  num_leaves=7, min_data_in_leaf=5)
    with pytest.raises(NonFiniteError):
        lgb.train(params, lgb.Dataset(X, label=y, params=params),
                  num_boost_round=6,
                  fobj=nan_gradient_fobj(bad_iters=[2]))
    with open(obs.trace_path()) as fh:
        events = json.load(fh)["traceEvents"]
    assert any(e["name"] == "train" for e in events)
    assert any(e["name"] == "nan_policy" for e in events)
    assert obs.snapshot()["counters"]["nan.raised"] == 1


# ------------------------------------------------------------------ CLI knob

def test_cli_double_dash_flags_normalize():
    from lightgbm_tpu.cli import parse_args
    params = parse_args(["--telemetry-dir=/tmp/t", "task=train"])
    assert params["telemetry_dir"] == "/tmp/t"
    assert params["task"] == "train"
    # only the KEY normalizes: dashes in the VALUE must survive
    params = parse_args(["--telemetry-dir=/data/run-1",
                         "--data=/path/my-file.csv"])
    assert params["telemetry_dir"] == "/data/run-1"
    assert params["data"] == "/path/my-file.csv"
