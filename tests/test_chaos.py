"""End-to-end fault-tolerance integration tests (docs/Fault-Tolerance.md):
kill-and-resume bit-identity on the serial and data-parallel paths, the
three ``nan_policy`` branches driven by chaos-injected NaN/Inf gradients
through ``engine.train``, and the loud config-fingerprint mismatch.

Run with ``make chaos`` (pinned LGBM_TPU_CHAOS_SEED); fast enough to ride
inside tier-1 as well.
"""
import logging

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.robustness.chaos import nan_gradient_fobj
from lightgbm_tpu.robustness.checkpoint import CheckpointError
from lightgbm_tpu.robustness.numeric import NonFiniteError
from lightgbm_tpu.utils.log import LightGBMError

pytestmark = pytest.mark.chaos


def _data(n=600, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 3) + 0.1 * rng.randn(n)).astype(
        np.float64)
    return X, y


# bagging on purpose: resume must restore the RNG key and carried bag mask
# exactly, or the continued run diverges immediately
BASE = dict(objective="regression", num_leaves=15, learning_rate=0.1,
            min_data_in_leaf=5, verbose=-1, metric="none", seed=17,
            bagging_fraction=0.8, bagging_freq=1)


# ------------------------------------------------------------ kill-and-resume

@pytest.mark.parametrize("tree_learner", [
    "serial", pytest.param("data", marks=pytest.mark.slow)])
def test_kill_and_resume_bit_identical(tmp_path, tree_learner):
    """Training killed between checkpoints, restarted with the identical
    command (resume_from=auto), must produce bit-identical model text to an
    uninterrupted run — on both the serial and the virtual-device
    data-parallel path."""
    X, y = _data()
    params = dict(BASE, tree_learner=tree_learner)
    straight = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=8).model_to_string()

    ck = dict(params, checkpoint_dir=str(tmp_path), checkpoint_interval=2)
    # "kill" at iteration 5: the run stops after 5 iterations, so the last
    # snapshot on disk is the interval-2 checkpoint at iteration 4 — resume
    # discards iteration 5's tree and replays from 4
    lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=5)
    resumed = lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=8,
                        resume_from="auto")
    assert resumed.num_trees() == 8
    assert resumed.model_to_string() == straight


def test_resume_from_auto_starts_fresh_without_checkpoints(tmp_path):
    X, y = _data(n=300)
    ck = dict(BASE, checkpoint_dir=str(tmp_path / "empty"))
    bst = lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=3,
                    resume_from="auto")
    assert bst.num_trees() == 3


def test_resume_rejects_different_dataset_of_same_shape(tmp_path):
    """The config fingerprint excludes data PATHS, so a resume pointed at a
    shape-compatible but different dataset must be caught by the dataset
    fingerprint instead of silently corrupting the model."""
    X, y = _data(n=300)
    ck = dict(BASE, checkpoint_dir=str(tmp_path), checkpoint_interval=2)
    lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=2)
    X2, y2 = _data(n=300, seed=99)           # same shape, different rows
    with pytest.raises(LightGBMError, match="dataset mismatch"):
        lgb.train(ck, lgb.Dataset(X2, label=y2), num_boost_round=4,
                  resume_from="auto")


def test_dart_rejects_checkpoint_config():
    """dart + checkpoint knobs must fail at config time — not 10 iterations
    in, when the interval callback hits the save-time check."""
    with pytest.raises(LightGBMError, match="dart"):
        lgb.Config.from_params(dict(boosting="dart", checkpoint_dir="/ck"))
    with pytest.raises(LightGBMError, match="dart"):
        lgb.Config.from_params(dict(boosting="dart", resume_from="auto"))


def test_resume_rejects_semantic_config_change(tmp_path):
    """A resumed run whose training semantics differ must fail loudly,
    naming the mismatched fields — silently mixing forests grown under
    different configs is the corruption this check exists to catch."""
    X, y = _data(n=300)
    ck = dict(BASE, checkpoint_dir=str(tmp_path), checkpoint_interval=2)
    lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=2)
    with pytest.raises(CheckpointError, match="num_leaves"):
        lgb.train(dict(ck, num_leaves=31), lgb.Dataset(X, label=y),
                  num_boost_round=4, resume_from="auto")


# ------------------------------------------------------- nan_policy branches

def _nan_params(policy, **extra):
    # objective="none" routes the chaos fobj's poisoned gradients into the
    # custom step; boost_from_average off keeps preds = raw scores
    out = dict(objective="none", verbose=-1, metric="none",
               boost_from_average=False, nan_policy=policy)
    out.update(extra)                    # extras may override (e.g. verbose)
    return out


def test_nan_policy_raise_fails_loudly_with_clean_state():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    fobj = nan_gradient_fobj(bad_iters=[2])
    with pytest.raises(NonFiniteError, match="gradients"):
        lgb.train(_nan_params("raise"), ds, num_boost_round=6, fobj=fobj)


def test_nan_policy_skip_iter_drops_poisoned_iterations(caplog):
    X, y = _data()
    fobj = nan_gradient_fobj(bad_iters=[1, 3], mode="inf")
    # verbose=0, not -1: this test ASSERTS the skip warnings are emitted,
    # and verbosity is wired into Log.set_level now (verbose=-1 silences)
    with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
        bst = lgb.train(_nan_params("skip_iter", verbose=0),
                        lgb.Dataset(X, label=y),
                        num_boost_round=6, fobj=fobj)
    assert bst.num_trees() == 4            # 6 rounds - 2 dropped iterations
    assert np.isfinite(bst.predict(X)).all()
    skips = [r for r in caplog.records
             if "skip_iter: dropped iteration" in r.getMessage()]
    assert len(skips) == 2


def test_nan_policy_skip_iter_aborts_on_deterministic_poison():
    X, y = _data(n=300)
    fobj = nan_gradient_fobj(bad_iters=range(100))     # every iteration bad
    with pytest.raises(NonFiniteError, match="consecutive"):
        lgb.train(_nan_params("skip_iter"), lgb.Dataset(X, label=y),
                  num_boost_round=30, fobj=fobj)


def test_nan_policy_clip_sanitizes_and_continues(caplog):
    X, y = _data()
    fobj = nan_gradient_fobj(bad_iters=[1], frac=0.02)
    # verbose=0: the clip warning must survive the wired verbosity
    with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
        bst = lgb.train(_nan_params("clip", verbose=0),
                        lgb.Dataset(X, label=y),
                        num_boost_round=6, fobj=fobj)
    assert bst.num_trees() == 6            # nothing dropped
    assert np.isfinite(bst.predict(X)).all()
    assert any("nan_policy=clip" in r.getMessage() for r in caplog.records)


def test_nan_policy_none_is_the_default_and_unguarded():
    X, y = _data(n=300)
    bst = lgb.train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=2,
                    keep_training_booster=True)
    assert bst._gbdt.nan_policy == "none"


def test_dart_rejects_gated_policies():
    X, y = _data(n=300)
    with pytest.raises(LightGBMError, match="dart"):
        lgb.train(dict(BASE, boosting="dart", nan_policy="skip_iter"),
                  lgb.Dataset(X, label=y), num_boost_round=2)


def test_dart_rejects_checkpointing(tmp_path):
    X, y = _data(n=300)
    params = dict(BASE, boosting="dart")
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=2,
                    keep_training_booster=True)
    with pytest.raises(LightGBMError, match="dart"):
        bst.save_checkpoint(str(tmp_path))


# ------------------------------------------- telemetry under fault injection
# (docs/Observability.md): the comm retry/timeout counters and the
# nan_policy event counters must increment under ChaosKVClient injection
# and land in the JSONL event stream.

import pickle  # noqa: E402

from lightgbm_tpu import observability as obs  # noqa: E402
from lightgbm_tpu.observability.export import read_jsonl  # noqa: E402
from lightgbm_tpu.parallel import comm  # noqa: E402
from lightgbm_tpu.robustness.chaos import (ChaosKVClient,  # noqa: E402
                                           ChaosPlan, FakeKVStore)
from lightgbm_tpu.robustness.retry import CommTimeoutError  # noqa: E402


@pytest.fixture
def telemetry(tmp_path):
    obs.reset_for_tests()
    obs.configure(telemetry_dir=str(tmp_path))
    yield obs
    obs.reset_for_tests()


def _preloaded_store(tag, peer_obj):
    store = FakeKVStore()
    key = f"lgbm_hostgather/{tag}/{comm._host_allgather_seq[0]}"
    store.preload(f"{key}/1", pickle.dumps(peer_obj))
    return store


def test_comm_fault_counters_land_in_jsonl(telemetry):
    # transient injected drop -> one retry, gather still succeeds
    chaos = ChaosKVClient(_preloaded_store("tel1", "peer"),
                          ChaosPlan(seed=11, drop_gets=(0,)))
    out = comm.host_allgather("mine", "tel1", timeout_ms=500,
                              client=chaos, rank=0, world=2)
    assert out == ["mine", "peer"]
    # permanent injected drops -> exhausted retries -> CommTimeoutError
    chaos2 = ChaosKVClient(_preloaded_store("tel2", "peer"),
                           ChaosPlan(seed=12, drop_gets=(0, 1, 2)))
    with pytest.raises(CommTimeoutError):
        comm.host_allgather("mine", "tel2", timeout_ms=300,
                            client=chaos2, rank=0, world=2)
    snap = obs.snapshot()
    assert snap["counters"]["comm.retries"] >= 1
    assert snap["counters"]["comm.timeouts"] >= 1
    assert snap["counters"]["comm.failures"] >= 1
    assert snap["counters"]["comm.host_allgather"] == 2
    obs.flush()
    recs = read_jsonl(obs.jsonl_path())
    counters = [r for r in recs if r.get("type") == "counters"][-1]
    assert counters["counters"]["comm.retries"] >= 1
    assert counters["counters"]["comm.timeouts"] >= 1
    spans = [r for r in recs
             if r.get("type") == "span" and r["name"] == "comm"]
    assert spans and all(s["args"]["op"] == "host_allgather" for s in spans)
    assert any(s["args"].get("error") for s in spans)   # the timed-out one


def test_nan_policy_event_counters_land_in_jsonl(telemetry):
    X, y = _data()
    fobj = nan_gradient_fobj(bad_iters=[1, 3], mode="inf")
    lgb.train(_nan_params("skip_iter"), lgb.Dataset(X, label=y),
              num_boost_round=6, fobj=fobj)
    snap = obs.snapshot()
    assert snap["counters"]["nan.events"] == 2
    assert snap["counters"]["nan.skipped_iters"] == 2
    recs = read_jsonl(obs.jsonl_path())      # engine.train flushed already
    evs = [r for r in recs
           if r.get("type") == "event" and r["name"] == "nan_policy"]
    assert len(evs) == 2
    assert all(e["args"]["policy"] == "skip_iter" for e in evs)
    counters = [r for r in recs if r.get("type") == "counters"][-1]
    assert counters["counters"]["nan.skipped_iters"] == 2


# ======================================================================
# Self-healing chaos matrix (docs/Fault-Tolerance.md): every injected
# fault class — corrupt latest checkpoint, kill -9 mid-run/mid-write,
# injected hang, corrupted stream shard — must recover WITHOUT human
# intervention to a model bit-identical to a fault-free run, across the
# serial, 8-simulated-device data-parallel, and stream-residency paths.
# The in-process arms ride tier-1; the supervised subprocess arms are
# marked slow (`make chaos` runs both).

import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from lightgbm_tpu.robustness.supervisor import (EXIT_SHARD_CORRUPT,  # noqa: E402
                                                Supervisor)
from lightgbm_tpu.utils.hermetic import force_device_count_flags  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# mode -> (extra train params). Stream keeps the small-shape knobs so the
# 600-row harness cuts into real multi-shard stores.
MODES = {
    "serial": dict(tree_learner="serial"),
    "data8": dict(tree_learner="data"),
    "stream": dict(tpu_residency="stream", tpu_hist_chunk=64,
                   tpu_stream_shard_rows=64, tpu_row_compact=False),
}


def _corrupt_file(path, how, seed=5):
    raw = bytearray(open(path, "rb").read())
    if how == "truncate":
        raw = raw[: len(raw) // 3]
    else:
        rng = np.random.RandomState(seed)
        for pos in rng.randint(16, len(raw), size=8):
            raw[pos] ^= 0xFF
    open(path, "wb").write(bytes(raw))


# ------------------------------------------- corrupt-latest-then-resume

# tier-1 keeps the serial bitflip arm; the other residency/parallelism x
# corruption combinations are tier-2 (`slow`, still in `make check`)
@pytest.mark.parametrize("how,mode", [
    ("bitflip", "serial")] + [
    pytest.param(h, m, marks=pytest.mark.slow)
    for h in ("bitflip", "truncate") for m in sorted(MODES)
    if (h, m) != ("bitflip", "serial")])
def test_corrupt_latest_lineage_recovery(tmp_path, mode, how):
    """resume_from=auto walks back past a corrupt latest snapshot to the
    newest one that verifies, and the continued run is bit-identical to
    an uninterrupted one — on every residency/parallelism path."""
    from lightgbm_tpu import observability as obs
    X, y = _data()
    params = dict(BASE, **MODES[mode])
    straight = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=10).model_to_string()
    ck = dict(params, checkpoint_dir=str(tmp_path), checkpoint_interval=2,
              checkpoint_keep_last_n=0)
    lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=6)
    from lightgbm_tpu.robustness.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    latest = mgr.latest()
    _corrupt_file(latest, how)
    before = obs.snapshot()["counters"].get("fault.checkpoint_corrupt", 0)
    resumed = lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=10,
                        resume_from="auto")
    after = obs.snapshot()["counters"]["fault.checkpoint_corrupt"]
    assert after >= before + 1            # the fallback actually engaged
    assert resumed.num_trees() == 10
    assert resumed.model_to_string() == straight


def test_resume_auto_refuses_all_corrupt_lineage(tmp_path):
    """When EVERY snapshot is corrupt, auto-resume must fail loudly
    instead of silently retraining from scratch."""
    from lightgbm_tpu.robustness.checkpoint import CheckpointError
    X, y = _data(n=300)
    ck = dict(BASE, checkpoint_dir=str(tmp_path), checkpoint_interval=2,
              checkpoint_keep_last_n=0)
    lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=4)
    from lightgbm_tpu.robustness.checkpoint import CheckpointManager
    for _id, path in CheckpointManager(str(tmp_path)).list_checkpoints():
        _corrupt_file(path, "bitflip")
    with pytest.raises(CheckpointError, match="refusing to silently"):
        lgb.train(ck, lgb.Dataset(X, label=y), num_boost_round=6,
                  resume_from="auto")


# ------------------------------------------------ in-process hang injection

def test_watchdog_fires_on_injected_hang_in_engine_train(tmp_path,
                                                         monkeypatch):
    """The env-gated chaos hang wedges the loop AFTER the heartbeat; the
    watchdog monitor thread fires within the (short) timeout, dumps
    diagnostics, and — action=dump — training then completes normally."""
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    marker = tmp_path / "hang.marker"
    monkeypatch.setenv("LGBM_TPU_CHAOS_HANG", "2:1.2")
    monkeypatch.setenv("LGBM_TPU_CHAOS_HANG_MARKER", str(marker))
    X, y = _data(n=300)
    params = dict(BASE, hang_timeout_s=0.3, hang_median_factor=0.0,
                  hang_action="dump", checkpoint_dir=str(tmp_path),
                  checkpoint_interval=2)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    assert bst.num_trees() == 4            # dump action never kills the run
    assert marker.exists()                 # the hang really was injected
    snap = obs.snapshot()["counters"]
    assert snap.get("fault.hangs", 0) >= 1
    assert snap.get("fault.watchdog_dumps", 0) >= 1
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("watchdog_dump_")]
    assert dumps
    obs.reset_for_tests()


# ------------------------------------------------- supervised E2E recovery

def _write_csv(path, X, y):
    with open(path, "w") as fh:
        for i in range(len(y)):
            fh.write(",".join([f"{y[i]:.6g}"]
                              + [f"{v:.6g}" for v in X[i]]) + "\n")


def _cli_args(data, model, mode, n_rounds, ck_dir=None, extra=()):
    args = [f"data={data}", "task=train", "objective=regression",
            "num_leaves=15", "learning_rate=0.1", "min_data_in_leaf=5",
            "metric=none", "seed=17", "bagging_fraction=0.8",
            "bagging_freq=1", f"num_trees={n_rounds}", "verbose=-1",
            f"output_model={model}"]
    for k, v in MODES[mode].items():
        args.append(f"{k}={v}")
    if ck_dir:
        args += [f"checkpoint_dir={ck_dir}", "checkpoint_interval=2"]
    return args + list(extra)


def _child_env(mode, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = force_device_count_flags(
        env.get("XLA_FLAGS", ""), 8 if mode == "data8" else None)
    # children run from tmp_path: the checkout must be on their path (they
    # then place the compile cache in <checkout>/.jax_cache themselves)
    env["PYTHONPATH"] = REPO
    env.update(extra_env or {})
    return env


def _run_supervised(tmp_path, mode, n_rounds=24, extra_args=(),
                    extra_env=None, on_spawn=None, max_restarts=3):
    """Fault-free baseline via the in-process CLI, then the faulted arm
    under the supervisor with real child processes; returns
    (baseline_model_text, supervised_model_text, supervisor)."""
    from lightgbm_tpu.cli import main as cli_main
    X, y = _data()
    data = tmp_path / "train.csv"
    _write_csv(data, X, y)
    straight_model = tmp_path / "straight.txt"
    cli_main(_cli_args(data, straight_model, mode, n_rounds))
    ck_dir = tmp_path / "ck"
    sup_model = tmp_path / "supervised.txt"
    child_args = _cli_args(data, sup_model, mode, n_rounds,
                           ck_dir=ck_dir, extra=extra_args)
    env = _child_env(mode, extra_env)
    children = []

    def spawn(argv):
        proc = subprocess.Popen([sys.executable, "-m", "lightgbm_tpu"]
                                + list(argv), env=env, cwd=str(tmp_path))
        children.append(proc)
        if on_spawn:
            on_spawn(proc, len(children))
        return proc

    sup = Supervisor(child_args, max_restarts=max_restarts, seed=1234,
                     backoff_base_s=0.05, backoff_max_s=0.2,
                     spawn_fn=spawn)
    rc = sup.run()
    assert rc == 0, (rc, sup.report())
    return (straight_model.read_text(), sup_model.read_text(), sup)


@pytest.mark.slow
@pytest.mark.parametrize("mode", sorted(MODES))
def test_supervised_kill9_recovers_bit_identical(tmp_path, mode):
    """A real SIGKILL once training has banked >= 2 checkpoints: the
    supervisor relaunches with resume_from=auto and the final model is
    bit-identical to the fault-free run; recovery time (MTTR) is
    measured."""
    from lightgbm_tpu.robustness.chaos import kill_after_checkpoints

    def kill_after_two_ckpts(proc, child_no):
        if child_no == 1:                  # SIGKILL, mid-run
            kill_after_checkpoints(proc, str(tmp_path / "ck"), n=2,
                                   timeout_s=120)

    straight, supervised, sup = _run_supervised(
        tmp_path, mode, on_spawn=kill_after_two_ckpts)
    assert supervised == straight
    assert sup.restarts >= 1
    assert sup.exit_codes[0] == -9
    assert sup.recovery_seconds            # MTTR actually measured


@pytest.mark.slow
def test_supervised_hang_watchdog_abort_recovers_bit_identical(tmp_path):
    """An injected mid-run hang (a stand-in for a wedged collective): the
    child's watchdog aborts-to-checkpoint with exit 142, the supervisor
    relaunches, the marker keeps the relaunch clean, and the final model
    is bit-identical to the fault-free run."""
    from lightgbm_tpu.robustness.watchdog import EXIT_HANG
    marker = tmp_path / "hang.marker"
    straight, supervised, sup = _run_supervised(
        tmp_path, "serial",
        extra_args=("hang_timeout_s=1.0", "hang_median_factor=0",
                    "hang_action=abort"),
        extra_env={"LGBM_TPU_CHAOS_HANG": "6:300",
                   "LGBM_TPU_CHAOS_HANG_MARKER": str(marker)})
    assert supervised == straight
    assert sup.restarts >= 1
    assert sup.exit_codes[0] == EXIT_HANG
    assert marker.exists()


@pytest.mark.slow
def test_supervised_shard_corruption_recovers_bit_identical(tmp_path):
    """A bit-flipped host shard under tpu_residency=stream: the CRC check
    turns it into exit 144, the supervisor relaunches, the rebuilt shard
    store is clean, and the final model is bit-identical."""
    marker = tmp_path / "shard.marker"
    straight, supervised, sup = _run_supervised(
        tmp_path, "stream",
        extra_env={"LGBM_TPU_CHAOS_FLIP_SHARD": str(marker)})
    assert supervised == straight
    assert sup.restarts >= 1
    assert sup.exit_codes[0] == EXIT_SHARD_CORRUPT
    assert marker.exists()
