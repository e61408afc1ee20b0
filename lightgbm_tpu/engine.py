"""train() / cv() entry points (reference: python-package/lightgbm/engine.py:18,310)."""
from __future__ import annotations

import collections
import os
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .config import Config
from .utils.log import Log


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: Union[str, List[str]] = "auto",
          categorical_feature: Union[str, List] = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates=None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume_from: Optional[str] = None) -> Booster:
    """Mirror of reference engine.py:18 lgb.train.

    Fault-tolerance additions (docs/Fault-Tolerance.md): ``resume_from``
    (also settable as a param) replays a checkpoint written by
    ``Booster.save_checkpoint`` before the first iteration — ``"auto"``
    resumes the latest snapshot in ``checkpoint_dir`` when one exists and
    starts fresh otherwise, so a preempted run restarts with the identical
    command. With ``checkpoint_dir`` + ``checkpoint_interval`` set, a
    snapshot is written every N iterations."""
    # persistent XLA compile cache (utils/cache.py): repeated runs pay each
    # step compile once
    from .utils.cache import resolve_compile_cache
    resolve_compile_cache()

    params = dict(params or {})
    # verbosity -> Log.set_level BEFORE construction so construction-time
    # messages (EFB, kernel resolution, unknown-parameter warnings) already
    # honor it; the resolved config value is re-applied below. Only the
    # canonical name and its alias are peeked — full alias resolution
    # happens (with its own warnings) inside Config.from_params.
    _v = params.get("verbose", params.get("verbosity"))
    if _v is not None:
        try:
            Log.set_level(int(_v))
        except (TypeError, ValueError):
            pass
    # telemetry config BEFORE booster construction: the booster_init event
    # and construction-time counters must land in the recording
    # (lightgbm_tpu/observability, docs/Observability.md)
    from . import observability as obs
    obs.maybe_configure_from_env()
    if params.get("telemetry_dir"):
        obs.configure(telemetry_dir=str(params["telemetry_dir"]))
    if "num_iterations" not in params and "num_boost_round" not in params:
        params["num_iterations"] = num_boost_round
    if early_stopping_rounds is not None:
        params["early_stopping_round"] = early_stopping_rounds
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    prev_booster: Optional[Booster] = None
    if init_model is not None:
        prev_booster = init_model if isinstance(init_model, Booster) \
            else Booster(params=params, model_file=init_model)

    booster = Booster(params=params, train_set=train_set)
    config = booster.config
    # the reference's verbosity semantics (utils/log.py Log.set_level):
    # <0 fatal-only, 0 warnings, 1 info, >1 debug — wired from the resolved
    # config on every train entry (cli.py and sklearn.py wire their own)
    Log.set_level(config.verbose)
    n_rounds = config.num_iterations

    valid_sets = valid_sets or []
    names = []
    for i, vs in enumerate(valid_sets):
        name = valid_names[i] if valid_names else f"valid_{i}"
        if vs is train_set:
            booster._gbdt.config = booster._gbdt.config.replace(is_training_metric=True)
            names.append("training")
            continue
        if vs.reference is None:
            vs.reference = train_set
        booster.add_valid(vs, name)
        names.append(name)

    # ---- HBM pre-flight budget (observability/memory.py) -------------------
    # analytic wave-loop residency — pure host arithmetic, after the valid
    # sets are attached so their device footprint counts: one budget line,
    # plus a warning when the estimate exceeds device_memory() capacity
    from .observability import memory as obs_memory
    try:
        # residency-aware: a booster that auto-fell-back to
        # tpu_residency=stream reports per-shard (not full-N) codes and
        # only warns when even the streamed state misses the budget
        obs_memory.log_budget(obs_memory.hbm_preflight(booster._gbdt),
                              budget=obs_memory.hbm_budget_bytes(config))
    except Exception as e:                                   # noqa: BLE001
        Log.debug("HBM pre-flight estimate failed: %s: %s",
                  type(e).__name__, e)

    # resolved mesh (multichip): which axis the device mesh shards — the
    # tree_learner=auto outcome — and the per-device row residency, logged
    # once so a scaling run's provenance is in the training log
    _pctx = booster._gbdt.pctx
    if _pctx.mesh is not None:
        _rows_dev = (booster._gbdt.num_data_padded // _pctx.num_devices
                     if _pctx.axis_kind == "rows"
                     else booster._gbdt.num_data_padded)
        Log.info("multichip: %d-device mesh, tree_learner=%s shards the "
                 "%s axis (~%d resident rows/device)", _pctx.num_devices,
                 _pctx.strategy, _pctx.axis_kind, _rows_dev)

    # continued training: seed scores with the loaded model's raw predictions
    # (reference: input_model re-prediction, application.cpp:90-93) and keep
    # its trees so the saved model contains the full forest
    if prev_booster is not None and prev_booster.trees:
        Kp = max(prev_booster.num_model_per_iteration, 1)
        if Kp != booster._gbdt.num_models:
            Log.fatal("init_model has %d models per iteration, training config "
                      "has %d", Kp, booster._gbdt.num_models)
        # keep exactly the trees whose predictions seed the scores: predict()
        # honors the prev model's best_iteration, so truncate the kept forest
        # the same way or the saved model would disagree with training
        n_prev_iters = prev_booster.best_iteration \
            if prev_booster.best_iteration > 0 else len(prev_booster.trees) // Kp
        # continued training seeds from model predictions ONLY: drop the fresh
        # booster's boost-from-average bias (reference BoostFromAverage applies
        # only to an empty model, gbdt.cpp:357-377)
        if abs(booster._gbdt.init_score_value) > 1e-15:
            iv = booster._gbdt.init_score_value
            booster._gbdt.score = booster._gbdt.score - iv
            for _vs in booster._gbdt.valid_sets:
                _vs.score = _vs.score - iv
            booster._gbdt.init_score_value = 0.0
        raw = np.asarray(prev_booster.predict(train_set.raw_data, raw_score=True))
        raw = raw.T if raw.ndim == 2 else raw
        valid_raw = []
        for vs in valid_sets:
            if vs is train_set:
                continue
            vraw = np.asarray(prev_booster.predict(vs.raw_data, raw_score=True))
            valid_raw.append(vraw.T if vraw.ndim == 2 else vraw)
        booster._gbdt.add_base_score(raw, valid_raw)
        booster._prev_trees = list(prev_booster.trees[: n_prev_iters * Kp])

    # ---- checkpoint/resume (robustness/checkpoint.py) ----------------------
    resume_from = resume_from or config.resume_from or None
    start_iter = 0
    if resume_from:
        if prev_booster is not None:
            Log.fatal("resume_from cannot be combined with init_model — a "
                      "checkpoint already contains the full training state")
        resolved = resume_from
        if resume_from == "auto":
            # lineage fallback (robustness/checkpoint.py): walk BACK to the
            # newest snapshot that passes its integrity check, so a
            # truncated/bit-flipped latest costs one checkpoint interval
            # instead of killing the resume (docs/Fault-Tolerance.md)
            from .robustness import distributed as _dist
            from .robustness.checkpoint import CheckpointManager
            resolved = None
            if config.checkpoint_dir and _dist.list_manifests(
                    config.checkpoint_dir):
                # gang manifests present: the GANG protocol owns auto —
                # every surviving rank resolves the same newest epoch ALL
                # of them can verify (or falls back a full epoch together;
                # robustness/distributed.py). A shrunk/solo restart over a
                # gang directory still resolves through the manifests, just
                # without the agreement round.
                gang = _dist.gang_env()
                client, rank, world = gang if gang is not None \
                    else (None, 0, 1)
                coord = _dist.GangCheckpointCoordinator(
                    config.checkpoint_dir, client=client, rank=rank,
                    world=world,
                    keep_last_n=config.checkpoint_keep_last_n,
                    elastic=config.elastic)
                resolved = coord.resolve_resume()
            elif config.checkpoint_dir:
                resolved = CheckpointManager(
                    config.checkpoint_dir).latest_verified()
            if resolved is None:
                Log.info("resume_from=auto: no checkpoint under %r — "
                         "starting fresh", config.checkpoint_dir)
        if resolved:
            booster.resume(resolved)
            start_iter = booster._gbdt.iter_
            if start_iter >= n_rounds:
                Log.warning("resumed checkpoint is already at iteration %d "
                            ">= num_iterations=%d — no further training",
                            start_iter, n_rounds)

    callbacks = list(callbacks or [])
    # chaos hang injection (robustness/chaos.py): env-gated one-shot
    # callback that wedges the loop where the watchdog heartbeat goes
    # quiet — a no-op without LGBM_TPU_CHAOS_HANG
    from .robustness.chaos import maybe_hang_callback
    _hang_cb = maybe_hang_callback()
    if _hang_cb is not None:
        callbacks.append(_hang_cb)
    if config.checkpoint_dir and config.checkpoint_interval > 0:
        # interval-CROSSING check, not modulo: under tree_batch>1 the
        # callback fires at batch boundaries whose iteration numbers jump
        # by K and may never hit an exact multiple of the interval
        _ck_state = {"last": start_iter}

        def _checkpoint_cb(env):
            if env.iteration + 1 - _ck_state["last"] >= config.checkpoint_interval:
                env.model.save_checkpoint()
                _ck_state["last"] = env.iteration + 1
        _checkpoint_cb.order = 40      # after record_evaluation (order 20):
        callbacks.append(_checkpoint_cb)   # the snapshot sees this iter's eval
    if learning_rates is not None:
        # reference engine.py: list-or-callable schedule routed through
        # the reset_parameter callback
        from .callback import reset_parameter
        callbacks.append(reset_parameter(learning_rate=learning_rates))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        if not booster._gbdt.valid_sets:
            Log.fatal("For early stopping, at least one validation dataset is required")
        from .callback import early_stopping
        callbacks.append(early_stopping(early_stopping_rounds))
    if isinstance(verbose_eval, bool):
        if verbose_eval:
            from .callback import log_evaluation
            callbacks.append(log_evaluation(1))
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        from .callback import log_evaluation
        callbacks.append(log_evaluation(verbose_eval))
    if evals_result is not None:
        from .callback import record_evaluation
        callbacks.append(record_evaluation(evals_result))
    # the booster's own eval history is always recorded — checkpoints carry
    # it so a resumed run's curves continue instead of restarting
    from .callback import record_evaluation as _rec
    callbacks.append(_rec(booster.eval_history))
    callbacks_before = [cb for cb in callbacks if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    gbdt = booster._gbdt
    eval_needed = bool(gbdt.valid_sets) or gbdt.config.is_training_metric or callbacks_after
    best_iteration = 0
    # ---- fused multi-tree steps (tree_batch, boosting/gbdt.py) -------------
    # K iterations per jit dispatch; metric eval, callbacks, checkpoints,
    # and early stopping land on batch boundaries. Custom objectives need a
    # host gradient round-trip per tree, so they force K=1 (loudly).
    tree_batch = getattr(gbdt, "tree_batch", 1)
    if fobj is not None and tree_batch > 1:
        Log.warning("tree_batch=%d needs a built-in objective (fobj requires "
                    "a host round-trip per tree); falling back to "
                    "tree_batch=1", tree_batch)
        tree_batch = 1
    if callbacks_before and tree_batch > 1:
        # before-iteration callbacks (reset_parameter — incl. the
        # learning_rates schedule) expect to retune EVERY iteration; under
        # fusion they would fire once per batch and the whole batch would
        # train on the batch-start parameters — a silently different model.
        Log.warning("tree_batch=%d is not supported with before-iteration "
                    "callbacks (learning_rates / reset_parameter retune "
                    "per iteration); falling back to tree_batch=1",
                    tree_batch)
        tree_batch = 1
    metric_freq = max(config.metric_freq, 1)
    # ---- telemetry (lightgbm_tpu/observability, docs/Observability.md) -----
    # span recording turned on above when a telemetry dir is configured
    # (param or LGBM_TPU_TELEMETRY_DIR); the metrics registry is always
    # live. The optional jax.profiler window (tpu_profile_iters) captures a
    # bounded iteration range at batch boundaries; it supersedes the
    # whole-run tpu_profile_dir trace (double-tracing is a jax error).
    from .observability.profiler import ProfileWindow, maybe_xla_trace
    if config.telemetry_dir:
        obs.configure(telemetry_dir=config.telemetry_dir)
    _profile_out = config.tpu_profile_dir or (
        os.path.join(obs.telemetry_dir(), "xprof")
        if obs.telemetry_dir() else "")
    profile_window = ProfileWindow(config.tpu_profile_iters, _profile_out)
    whole_run_profile = "" if profile_window.enabled \
        else config.tpu_profile_dir
    # compile-time cost capture (observability/costs.py) is opt-in — it
    # duplicates trace/compile work at every dispatch site it reports on.
    # The param scopes capture to THIS run: the prior state (env knob, an
    # explicit configure by the bench/smoke harness) is restored in the
    # finally below. Enabled DIRECTLY before the try so no setup failure
    # between enable and restore can leak capture into later fits.
    from .observability import costs as obs_costs
    _costs_was_enabled = None
    if config.tpu_cost_analysis:
        _costs_was_enabled = obs_costs.enabled()
        obs_costs.configure(enabled=True)
    # ---- hang watchdog (robustness/watchdog.py) ----------------------------
    # heartbeat-fed from the same host dispatch boundaries the span tracer
    # records: one beat per batch dispatch below, zero device syncs. A
    # wedged collective/transfer blocks the loop, the beats stop, and the
    # watchdog dumps diagnostics (hang_action=abort additionally exits 142
    # so the supervisor restarts from the last checkpoint).
    # ---- peer heartbeat lease (robustness/distributed.py) ------------------
    # under a live gang each rank beats a seq lease in the KV store at the
    # same dispatch boundaries the watchdog beats at, and probes the peers'
    # leases BEFORE entering each collective wave — a dead peer raises a
    # typed PeerLostError naming the rank instead of wedging the collective
    lease = None
    if config.gang_lease_timeout_s > 0:
        from .robustness import distributed as _dist
        _gang = _dist.gang_env()
        if _gang is not None:
            _cl, _rk, _wd = _gang
            lease = _dist.HeartbeatLease(
                client=_cl, rank=_rk, world=_wd,
                lease_timeout_s=config.gang_lease_timeout_s,
                interval_s=config.gang_heartbeat_interval_s)
            lease.beat(force=True)
            Log.info("gang heartbeat lease armed: rank %d/%d, interval "
                     "%.1fs, lease timeout %.1fs", _rk, _wd,
                     config.gang_heartbeat_interval_s,
                     config.gang_lease_timeout_s)
    watchdog = None
    if config.hang_timeout_s > 0:
        from .robustness.watchdog import HangWatchdog
        watchdog = HangWatchdog(
            timeout_s=config.hang_timeout_s,
            median_factor=config.hang_median_factor,
            action=config.hang_action,
            dump_dir=(obs.telemetry_dir() or config.checkpoint_dir or "."),
            attribution_fn=lease.attribution if lease is not None else None)
        watchdog.beat(start_iter)
        watchdog.start()
        Log.info("hang watchdog armed: timeout %.1fs, median factor %g, "
                 "action=%s", config.hang_timeout_s,
                 config.hang_median_factor, config.hang_action)
    try:
        with maybe_xla_trace(whole_run_profile), \
                obs.span("train", rows=gbdt.num_data, n_rounds=n_rounds,
                         start_iter=start_iter, tree_batch=tree_batch,
                         objective=config.objective):
            it = start_iter
            while it < n_rounds:
                k = min(tree_batch, n_rounds - it)
                profile_window.before_step(it, k)
                for cb in callbacks_before:
                    cb(CallbackEnv(booster, params, it, 0, n_rounds, None))
                if lease is not None:
                    # beat FIRST, then probe: the lease must advance before
                    # this rank disappears into a potentially long dispatch
                    # (first-step compiles run minutes), so peer ages
                    # measure inter-rank skew at the boundary — not
                    # iteration time. Then the pre-wave liveness probe
                    # detects a dead peer BEFORE dispatching the collective
                    # (PeerLostError names the rank; both are rate-limited
                    # inside, host-only, no device sync)
                    lease.beat()
                    lease.probe()
                if fobj is not None:
                    gbdt.train_one_iter_custom(fobj)
                else:
                    gbdt.train_batch(k)
                it_end = it + k
                profile_window.after_step(it_end)
                if watchdog is not None:
                    watchdog.beat(it_end)
                if lease is not None:
                    lease.beat()
                eval_results = []
                if gbdt.valid_sets or gbdt.config.is_training_metric:
                    # eval when the batch crossed a metric_freq boundary
                    # (== (it+1) % freq == 0 at k=1)
                    if it_end // metric_freq > it // metric_freq:
                        eval_results = gbdt.eval_all()
                        if feval is not None:
                            eval_results.extend(_run_feval(feval, gbdt, booster))
                        if gbdt._check_no_splits():
                            break
                for cb in callbacks_after:
                    cb(CallbackEnv(booster, params, it_end - 1, 0, n_rounds,
                                   eval_results))
                it = it_end
    except EarlyStopException as e:
        best_iteration = e.best_iteration + 1
        booster.best_score = e.best_score
    except Exception as e:
        # a peer that dies MID-wave (after the pre-wave probe) surfaces as
        # a raw XlaRuntimeError from the dead collective (gloo TCP reset,
        # coordination-service health poll) — map it onto the typed comm-
        # loss errors, naming the rank from the heartbeat leases, so the
        # CLI exits 145 and the fleet supervisor attributes the survivor
        from .robustness.retry import CommRetryError
        if lease is not None and not isinstance(e, CommRetryError):
            from .robustness.distributed import comm_loss_error
            typed = comm_loss_error(e, lease)
            if typed is not None:
                raise typed from e
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()
        if lease is not None:
            lease.withdraw()
        profile_window.close()
        # telemetry finalize + flush must never take the run down — and must
        # run on EVERY exit path (early stop, nan_policy=raise, comm errors)
        # so the trace on disk reflects what actually happened
        try:
            gbdt.publish_telemetry()
        except Exception as e:                               # noqa: BLE001
            Log.warning("telemetry publish failed: %s: %s",
                        type(e).__name__, e)
        try:
            obs.flush()
        except Exception as e:                               # noqa: BLE001
            Log.warning("telemetry flush failed: %s: %s",
                        type(e).__name__, e)
        # train-end snapshot dump (cost/memory reports included): the
        # explicit dump_snapshot path AND — whenever a telemetry dir is
        # configured — a snapshot_<pid>.json in that dir, unconditionally,
        # so a chip run's output directory captures it without code edits
        try:
            snap_paths = []
            if config.dump_snapshot:
                snap_paths.append(config.dump_snapshot)
            if obs.telemetry_dir():
                snap_paths.append(os.path.join(
                    obs.telemetry_dir(), f"snapshot_{os.getpid()}.json"))
            for snap_path in snap_paths:
                obs.write_snapshot(snap_path)
        except Exception as e:                               # noqa: BLE001
            Log.warning("snapshot dump failed: %s: %s",
                        type(e).__name__, e)
        if _costs_was_enabled is False:
            obs_costs.configure(enabled=False)

    booster._finalize()
    if config.tpu_time_tag or os.environ.get("LGBM_TPU_TIMETAG"):
        # the reference's TIMETAG destructor dump (gbdt.cpp), as a view of
        # the registry's always-on records
        Log.info("%s", obs.time_tag_summary())
    if best_iteration:
        # best_iteration indexes the FULL forest (prev + new): predict()
        # slices self.trees from the front
        n_prev = len(getattr(booster, "_prev_trees", [])) // \
            max(booster._gbdt.num_models, 1)
        booster.best_iteration = best_iteration + n_prev
    if not keep_training_booster:
        # reference engine.py:222-224: the returned booster releases its
        # training buffers (host trees are already detached from device state,
        # so no model-string round-trip is needed)
        booster.free_dataset()
    return booster


def _run_feval(feval, gbdt, booster):
    out = []
    import numpy as np
    for vs in gbdt.valid_sets:
        preds = np.asarray(gbdt._convert(vs.score)).reshape(-1)
        res = feval(preds, vs)
        if isinstance(res, tuple):
            res = [res]
        for name, value, hib in res:
            out.append((vs.name, name, value, hib))
    return out


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference engine.py:310)."""
    params = dict(params or {})
    if early_stopping_rounds:
        params["early_stopping_round"] = early_stopping_rounds
    if metrics:
        params["metric"] = metrics
    train_set.construct(Config.from_params(train_set.params | params
                                           if isinstance(train_set.params, dict) else params))
    n = train_set.num_data()
    label = train_set.get_label()
    rng = np.random.default_rng(seed)

    group_sizes = None if train_set.group is None else np.asarray(train_set.group,
                                                                  dtype=np.int64)
    if folds is None and group_sizes is not None:
        # ranking: fold at QUERY granularity so group structure survives
        # (reference engine.py:310 _make_n_folds uses GroupKFold when the
        # dataset carries query boundaries)
        nq = len(group_sizes)
        if nfold > nq:
            raise ValueError(f"Cannot have number of folds={nfold} greater "
                             f"than the number of queries={nq}")
        q_order = np.arange(nq)
        if shuffle:
            rng.shuffle(q_order)
        bounds = np.concatenate([[0], np.cumsum(group_sizes)])
        q_chunks = np.array_split(q_order, nfold)

        def rows_of(queries):
            qs = np.sort(queries)
            return np.concatenate([np.arange(bounds[q], bounds[q + 1])
                                   for q in qs]) if len(qs) else np.array([], int)

        folds = [(rows_of(np.concatenate([c for j, c in enumerate(q_chunks)
                                          if j != f])),
                  rows_of(q_chunks[f])) for f in range(nfold)]
    if folds is None:
        idx = np.arange(n)
        if stratified and label is not None and len(np.unique(label)) <= max(32, int(params.get("num_class", 2))):
            folds_idx = [[] for _ in range(nfold)]
            for cls in np.unique(label):
                cls_idx = idx[label == cls]
                if shuffle:
                    rng.shuffle(cls_idx)
                for f in range(nfold):
                    folds_idx[f].extend(cls_idx[f::nfold])
            folds = [(np.setdiff1d(idx, np.array(te)), np.array(sorted(te)))
                     for te in folds_idx]
        else:
            if shuffle:
                rng.shuffle(idx)
            chunks = np.array_split(idx, nfold)
            folds = [(np.concatenate([c for j, c in enumerate(chunks) if j != f]),
                      chunks[f]) for f in range(nfold)]

    results: Dict[str, List[float]] = collections.defaultdict(list)
    fold_records = []
    qid = None if group_sizes is None else np.repeat(
        np.arange(len(group_sizes)), group_sizes)
    for tr_idx, te_idx in folds:
        tr = train_set.subset(tr_idx, params=dict(train_set.params))
        te_raw = train_set.raw_data[te_idx]
        te_label = None if label is None else label[te_idx]
        te_group = None if qid is None else group_sizes[np.unique(qid[te_idx])]
        te = Dataset(te_raw, label=te_label, group=te_group, reference=tr)
        evals_result: Dict = {}
        train(params, tr, num_boost_round=num_boost_round, valid_sets=[te],
              valid_names=["valid"], fobj=fobj, feval=feval,
              early_stopping_rounds=early_stopping_rounds,
              evals_result=evals_result, verbose_eval=False,
              callbacks=callbacks)
        fold_records.append(evals_result.get("valid", {}))

    if fold_records:
        for metric in fold_records[0]:
            lengths = [len(fr[metric]) for fr in fold_records if metric in fr]
            for i in range(min(lengths)):
                vals = [fr[metric][i] for fr in fold_records]
                results[f"{metric}-mean"].append(float(np.mean(vals)))
                results[f"{metric}-stdv"].append(float(np.std(vals)))
    return dict(results)
