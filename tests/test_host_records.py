"""The host's side of a tree, kept by the program itself with tracing OFF
(docs/Observability.md, "Always-on records"): one observation a call in
the per-call series, the collector's seconds, set-up tiled by spans, the
step's first call split into trace / lower / compile-or-load. None of it
reads a device value, and none of it is in the lowered step."""
import gc
import hashlib
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import observability as obs
from lightgbm_tpu.observability.tracer import _NULL_SPAN

PARAMS = dict(objective="binary", num_leaves=7, max_bin=15,
              min_data_in_leaf=5, verbose=-1, metric="none", device="cpu")


@pytest.fixture(autouse=True)
def registry():
    obs.reset_for_tests()
    yield obs.get_registry()
    obs.reset_for_tests()


def _data(n=600, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.65).astype(np.float32)
    return X, y


def _booster(**extra):
    X, y = _data()
    params = dict(PARAMS, **extra)
    return lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, params=params))


def _series(reg):
    return {name: reg.summary(name).values() for name in obs.STEP_SERIES}


def _logloss(preds, dataset):
    y = dataset.get_label()
    p = 1.0 / (1.0 + np.exp(-preds))
    return p - y, p * (1.0 - p)


def _drive(kind: str, calls: int):
    """``calls`` calls of the step by one of its four paths."""
    if kind == "k4":
        gbdt = _booster(tree_batch=4)._gbdt
        for _ in range(calls):
            gbdt.train_batch(4)
        return gbdt
    bst = _booster(**(dict(tpu_residency="stream", tpu_stream_shard_rows=256)
                      if kind == "stream" else {}))
    for _ in range(calls):
        bst.update(fobj=_logloss if kind == "custom" else None)
    return bst._gbdt


# ------------------------------------------------------ the per-call series

@pytest.mark.parametrize("kind", ["k1", "k4", "stream", "custom"])
def test_one_observation_a_call_in_call_order(registry, kind):
    calls = 3
    gbdt = _drive(kind, calls)
    assert gbdt.iter_ == calls * (4 if kind == "k4" else 1)
    series = _series(registry)
    assert {name: len(v) for name, v in series.items()} == {
        name: calls for name in obs.STEP_SERIES}
    # call order: the first call traced and compiled, and no later one did
    host = series["step.host_s"]
    assert host[0] == max(host)
    # the process's first call has no call before it
    assert series["step.gap_s"][0] == 0.0 and series["step.gc_s"][0] == 0.0
    assert all(g > 0 for g in series["step.gap_s"][1:])


@pytest.mark.parametrize("kind", ["k1", "k4", "stream", "custom"])
def test_host_seconds_are_the_sum_of_their_parts(registry, kind):
    _drive(kind, 5)
    s = _series(registry)
    outside = []
    for i in range(5):
        parts = (s["step.host.prep_s"][i] + s["step.host.launch_s"][i]
                 + s["step.host.post_s"][i])
        assert 0 < parts <= s["step.host_s"][i]
        outside.append(s["step.host_s"][i] - parts)
    # to a millisecond; the least of five, since a collection or a
    # descheduled worker can fall between two parts of any one call
    assert min(outside) < 1e-3, (kind, outside)
    # the jitted call's first run is where the launch's seconds are
    assert s["step.host.launch_s"][0] == max(s["step.host.launch_s"])


def test_dart_is_one_observation_a_call_too(registry):
    bst = _booster(boosting="dart", drop_rate=0.5)
    for _ in range(3):
        bst.update()
    assert [len(v) for v in _series(registry).values()] == [3] * 6


def test_gap_is_the_callers_time_between_two_calls(registry):
    bst = _booster()
    bst.update()
    bst.update()
    time.sleep(0.3)
    bst.update()
    gaps = registry.summary("step.gap_s").values()
    assert gaps[2] >= 0.3 and gaps[2] == max(gaps)


def test_a_forced_collection_lands_in_its_dispatch_and_no_other(registry):
    bst = _booster()
    bst.update()                # installs the hook: set-up never pays
    assert any(getattr(cb, "__self__", None) is obs._steps
               for cb in gc.callbacks)
    bst.update()
    gc.disable()                # only the forced collection below runs
    try:
        bst.update()            # call 2
        bst.update()            # call 3
        junk = [[i] for i in range(200_000)]
        gc.collect()            # between call 3's return and call 4's entry
        del junk
        bst.update()            # call 4: its entry closes call 3's interval
        bst.update()            # call 5
    finally:
        gc.enable()
    gc_s = registry.summary("step.gc_s").values()
    assert len(gc_s) == 6
    assert gc_s[4] > 0 and gc_s[3] == 0.0 and gc_s[5] == 0.0
    # the series is the whole record: no counter repeats its sum
    assert not [k for k in registry.snapshot()["counters"]
                if k.startswith("host.")]
    # of the dispatch it fell in, the collector's seconds are a part
    assert gc_s[4] <= registry.summary("step.gap_s").values()[4]


def test_the_hook_is_one_a_process_and_set_up_never_installs_it(registry):
    hooked = lambda: [cb for cb in gc.callbacks          # noqa: E731
                      if getattr(cb, "__self__", None) is obs._steps]
    a, b = _booster(), _booster(num_leaves=5)
    assert hooked() == []
    a.update(), b.update(), a.update()
    assert len(hooked()) == 1


# ----------------------------------------------------------- set-up, tiled

def test_unnamed_is_the_wholes_minus_their_children(registry):
    _booster()
    g = registry.snapshot()["gauges"]
    dataset = ("setup.dataset_columns_s", "setup.dataset_find_bins_s",
               "setup.dataset_ingest_check_s",
               "setup.dataset_bin_host_s", "setup.dataset_metadata_s")
    booster = [f"setup.booster_{s}_s" for s in (
        "mesh", "objective_init", "shapes", "efb", "layout", "fingerprint",
        "place_codes", "place", "spec", "init_score", "state")]
    assert all(g.get(name) is not None for name in (*dataset, *booster)), g
    wholes = g["setup.dataset_construct_s"] + g["setup.booster_init_s"]
    children = sum(g[name] for name in (*dataset, *booster))
    assert g["setup.unnamed_s"] >= 0
    assert g["setup.unnamed_s"] == pytest.approx(wholes - children, abs=1e-6)
    # the boundaries the operator's summary prints are histograms too (the
    # sum over the process); a child is a gauge alone
    h = {name: rec for name, rec in registry.snapshot()["histograms"].items()
         if name.startswith("setup.")}
    assert sorted(h) == ["setup.booster_init_s", "setup.dataset_construct_s"]
    assert all(rec["count"] == 1 and rec["sum"] == pytest.approx(
        g[name], abs=1e-6) for name, rec in h.items())


def test_a_valid_set_is_no_whole_of_set_up(registry):
    """A dataset with ``reference=`` is constructed after ``booster.init``
    (``add_valid``): it is a boundary of its own, so the training set's
    gauge, ``setup.program_s`` and ``setup.unnamed_s`` stay the training
    set's."""
    X, y = _data()
    train = lgb.Dataset(X, label=y, params=PARAMS)
    bst = lgb.Booster(params=PARAMS, train_set=train)
    before = registry.snapshot()["gauges"]
    for k in (1, 2):
        bst.add_valid(lgb.Dataset(X[:50 * k], label=y[:50 * k],
                                  reference=train), f"v{k}")
    snap = registry.snapshot()
    # (``add_valid`` also finalizes: ``setup.finalize_fetch_s``)
    assert {k: v for k, v in snap["gauges"].items()
            if k not in ("setup.dataset_construct_valid_s",
                         "setup.finalize_fetch_s")} == before
    assert snap["histograms"]["setup.dataset_construct_s"]["count"] == 1
    assert snap["histograms"]["setup.dataset_construct_valid_s"]["count"] == 2


def test_a_child_of_a_child_is_counted_once(registry):
    with obs.setup_span("booster.init"):
        with obs.setup_span("booster.place_codes"):
            time.sleep(0.01)
            with obs.setup_span("ingest"):
                time.sleep(0.02)
        time.sleep(0.01)
    g = registry.snapshot()["gauges"]
    assert g["setup.ingest_s"] >= 0.02
    assert g["setup.booster_place_codes_s"] >= 0.03
    assert g["setup.unnamed_s"] >= 0.01
    assert g["setup.unnamed_s"] == pytest.approx(
        g["setup.booster_init_s"] - g["setup.booster_place_codes_s"])


def test_stages_end_with_the_error_that_ends_them(registry):
    with pytest.raises(RuntimeError):
        with obs.setup_span("booster.init"), obs.setup_stages() as stage:
            stage("booster.mesh")
            stage("booster.efb")
            raise RuntimeError("no plan")
    g = registry.snapshot()["gauges"]
    assert {"setup.booster_mesh_s", "setup.booster_efb_s",
            "setup.booster_init_s", "setup.unnamed_s"} <= set(g)
    assert obs._setup_open == []


# ------------------------------------------------ the first call, split

def test_compile_split_is_a_union_not_a_sum():
    fired = [("trace", 1.0, 2.0), ("trace", 0.5, 3.0),     # inner, then outer
             ("trace", 4.0, 4.5), ("lower", 3.0, 3.25),
             ("backend", 5.0, 7.0), ("cache_load", 5.5, 6.0)]
    assert obs.compile_split(fired) == {
        "trace": 3.0, "lower": 0.25, "backend": 2.0, "cache_load": 0.5}
    assert set(obs.compile_split([]).values()) == {0.0}


FIRST_CALL = ("compile.step_first_call_s", "compile.step_trace_s",
              "compile.step_lower_s", "compile.step_backend_s")


def test_a_new_executable_moves_the_first_call_counters(registry):
    def counters():
        c = registry.snapshot()["counters"]
        return {k: c.get(k, 0.0) for k in
                FIRST_CALL + ("compile.step_executables",)}

    a = _booster()
    assert counters()["compile.step_first_call_s"] == 0.0   # set-up: none
    a.update()
    first = counters()
    assert first["compile.step_executables"] == 1
    assert all(first[k] > 0 for k in FIRST_CALL), first
    # jax's own durations fired inside the call they are attributed to
    assert (first["compile.step_trace_s"] + first["compile.step_lower_s"]
            + first["compile.step_backend_s"]
            <= first["compile.step_first_call_s"])
    launch = registry.summary("step.host.launch_s").values()[0]
    # one clock: the launch part's own start (``timed_span`` yields it)
    assert 0 < first["compile.step_first_call_s"] <= launch
    # a steady dispatch moves none of them
    a.update()
    assert counters() == first
    # another num_leaves is another step: they all move again
    b = _booster(num_leaves=5)
    b.update()
    second = counters()
    assert second["compile.step_executables"] == 2
    assert all(second[k] > first[k] for k in FIRST_CALL), (first, second)
    b.update(), a.update()
    assert counters() == second


def test_the_event_carries_the_split(registry, tmp_path):
    obs.configure(telemetry_dir=str(tmp_path))
    _booster().update()
    ev = [e for e in obs.get_tracer().events()
          if e["name"] == "compile.step_trace"]
    assert len(ev) == 1
    assert {"first_call_s", "trace_s", "lower_s", "backend_s",
            "cache_load_s"} <= set(ev[0]["args"])
    assert ev[0]["args"]["first_call_s"] >= ev[0]["args"]["trace_s"] > 0


# ----------------------------------------------------- what "off" still is

def test_with_tracing_off_a_span_is_still_the_shared_no_op(registry):
    """The overhead contract: no telemetry directory, no profiler session
    -> ``span()`` hands back one shared object and records nothing; the
    always-on records ride beside it, in the registry."""
    assert not obs.enabled() and obs.telemetry_dir() is None
    assert obs.span("step.prep") is _NULL_SPAN
    bst = _booster()
    bst.update()
    bst.update()
    assert obs.get_tracer().events() == []
    assert registry.summary("step.host_s").count == 2
    with obs.step_part("prep"), obs.timed_span("eval", lambda s: None):
        assert obs.span("anything") is _NULL_SPAN


# the parent's lowered step (PR 37's commit; jax 0.9.0, CPU), 21
# configurations compared by hand in PR 38 and two pinned here: the
# always-on records sit outside the jitted function, so its text cannot
# move. A PR that changes the step ON PURPOSE re-pins these
# (hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest()).
LOWERED = {
    "serial": ("192149392ad1b2ea7ce4b3cea6c4ef71"
               "c78f4a6c980ba770604ba106104d8a7c", {}),
    "k4": ("e9caaebb3a8d91a4b682da38134809ce"
           "676aa080c7b6a8b747a1668bd8d6a7c0", dict(tree_batch=4)),
}


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_the_lowered_step_is_the_parents_text(name):
    sha, extra = LOWERED[name]
    params = dict(objective="binary", num_leaves=15, max_bin=31, verbose=-1,
                  min_data_in_leaf=5, metric="binary_logloss", device="cpu",
                  bagging_fraction=0.7, bagging_freq=1, tpu_compact_frac=0.5,
                  **extra)
    rng = np.random.RandomState(2)
    X = rng.rand(2500, 8).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(lgb.Dataset(X[:300], label=y[:300], reference=ds), "v")
    g = bst._gbdt
    consts, valid_Xb, valid_scores = g._dispatch_prep(g._step_shrinkage())
    args = (consts, valid_Xb, g.score, valid_scores, g.bag_mask, g._rng_key,
            g._iter_dev, g._shrink_cache[1])
    text = g._make_step(batch=params.get("tree_batch", 1)).lower(
        *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha
