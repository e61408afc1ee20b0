"""ServingEngine: AOT-compiled, bucket-padded forest inference.

The production inference path (ROADMAP item 4, docs/Serving.md). A model
loaded from ANY interchange format — protobuf (``io/model_proto.py``, the
reference fork's headline feature), LightGBM text, JSON dump, or an
in-memory ``Booster`` — is stacked ONCE into the rank-encoded
``StackedForest`` arrays (``ops/predict.py``), placed on device once, and
walked through a per-engine jitted ``forest_walk_leaves`` whose input
shapes are drawn from a fixed **batch-size bucket ladder**: every request
is padded up to the smallest bucket that holds it, so million-user traffic
shapes — many small concurrent batches, never one big one — hit a finite,
warmed set of executables and NEVER recompile in steady state
(``bench.py --serve`` pins this under a RecompileGuard). ``warmup()``
compiles every bucket ahead of serving; with the persistent XLA compile
cache (``utils/cache.resolve_compile_cache``) a restarted server replays
the compiles from disk.

Numerics contract: traversal is integer-exact on device (rank compares);
leaf-value accumulation happens on the HOST in float64, sequentially in
tree order — served predictions are **bit-identical** to the training
booster's host ``predict()`` (pinned in tests/test_serving.py, including
the protobuf round trip). The one device->host sync per dispatch — the
result fetch — is the contract; tpu-lint R011 keeps any other host sync
out of this package (the sync below is baseline-exempt).

Resilience (docs/Serving.md "Resilience", serving/resilience.py): the
model lives in an immutable ``_ModelState`` snapshot read ONCE per
request, so a hot ``reload()`` — AOT-compile the candidate off to the
side, verify it bit-identical against its own booster on a held sample,
swap atomically, roll back on any failure — never mixes versions inside
a request. Device-dispatch failures land on a ``CircuitBreaker``: after
``serve_breaker_failures`` failures in ``serve_breaker_window_s`` the
engine degrades to the host predictor (correct answers, host throughput)
while a daemon probe re-warms the device path; ``health()`` reports
``ready|degraded|down`` for load-balancer integration.

Categorical forests cannot take the rank-encoded walk and serve through
the host predictor instead (one-time warning from
``ops/predict.forest_predict_raw`` — same engine API, host throughput).

Observability: every request lands in the process registry —
``serve.requests``/``serve.rows`` counters, ``serve.batch_fill_frac``
histogram, ``serve.latency_ms``/``serve.dispatch_ms`` quantile summaries
whose p50/p99 surface in ``observability.snapshot()`` — plus the
resilience series: ``serve.host_fallback``/``serve.breaker_trips``/
``serve.breaker_recoveries``/``serve.reloads``/``serve.reload_rollbacks``
counters and the ``serve.health``/``serve.model_version`` gauges.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import observability as obs
from ..config import Config
from ..utils.log import Log
from .resilience import (CircuitBreaker, DeadlineExceededError,
                         DeviceDispatchError, ReloadError, ServingClosedError)

_HEALTH_CODE = {"ready": 0, "degraded": 1, "down": 2}


def bucket_ladder(config) -> List[int]:
    """Resolve the batch-size bucket ladder from config.

    ``serve_buckets`` (comma list, strictly ascending) wins; empty = the
    powers-of-two ladder 1, 2, 4, ... up to ``serve_max_batch_rows`` —
    dense enough that padding never exceeds 2x (the batch_fill_frac floor
    is 0.5)."""
    if config.serve_buckets:
        out = [int(v) for v in str(config.serve_buckets).split(",") if v]
        return out
    out, b = [], 1
    while b < config.serve_max_batch_rows:
        out.append(b)
        b *= 2
    out.append(int(config.serve_max_batch_rows))
    return out


class _ModelState:
    """One immutable serving model: booster + stacked forests + device
    arrays + the per-state jitted walk. Requests snapshot the engine's
    current state ONCE and use only it, so an atomic state swap
    (``reload``) can never mix two model versions inside one request."""

    __slots__ = ("booster", "config", "trees", "num_class_models",
                 "num_iteration", "num_features", "forests",
                 "has_categorical", "dev", "walk", "version", "warmed")

    def __init__(self, booster, num_iteration: Optional[int], version: int):
        import jax
        import jax.numpy as jnp

        from ..ops.predict import StackedForest, forest_walk_leaves

        self.booster = booster
        self.config = booster.config
        K = max(booster.num_model_per_iteration, 1)
        self.num_class_models = K
        if num_iteration is None or num_iteration <= 0:
            num_iteration = booster.best_iteration \
                if booster.best_iteration > 0 else len(booster.trees) // K
        self.num_iteration = num_iteration
        self.trees = booster.trees[: num_iteration * K]
        self.num_features = booster.num_total_features
        self.forests = [StackedForest(self.trees[k::K], self.num_features)
                        for k in range(K)]
        self.has_categorical = any(f.has_categorical for f in self.forests)
        self.dev: List[Tuple] = []
        if not self.has_categorical:
            # device residency: the stacked arrays upload ONCE here and are
            # reused by every dispatch (forest_predict_raw re-uploads per
            # call — fine for a one-shot batch, wrong for a serving loop)
            for f in self.forests:
                self.dev.append(tuple(jnp.asarray(a) for a in (
                    f.split_feature, f.thr_rank, f.decision, f.left, f.right,
                    f.root_is_leaf, f.zero_rank)))
            # per-state jit: the cache holds exactly this model's
            # (class, bucket) signatures, so a RecompileGuard registered on
            # it pins the zero-recompile serving contract
            self.walk = jax.jit(forest_walk_leaves)
        else:
            self.walk = None
        self.version = version
        self.warmed = False


class ServingEngine:
    """Load-once, compile-ahead, dispatch-forever forest inference."""

    def __init__(self, model, params: Optional[Dict] = None,
                 num_iteration: Optional[int] = None, warmup: bool = True):
        from ..utils.cache import resolve_compile_cache

        resolve_compile_cache()
        booster = self._load_booster(model, params)
        self.config = booster.config
        self.buckets = sorted(bucket_ladder(self.config))
        self.max_bucket = self.buckets[-1]
        self._model = _ModelState(booster, num_iteration, version=1)
        self._reload_lock = threading.Lock()
        self._closed = False
        # fault-injection hook (serving/resilience.py DispatchChaos):
        # invoked at the top of every device dispatch when installed
        self.chaos = None
        self._breaker = CircuitBreaker(
            failures=self.config.serve_breaker_failures,
            window_s=self.config.serve_breaker_window_s)
        self._probe_stop = threading.Event()
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_lock = threading.Lock()   # owns _probe_running
        self._probe_running = False
        reg = obs.get_registry()
        reg.gauge("serve.buckets").set(len(self.buckets))
        reg.gauge("serve.max_batch_rows").set(self.max_bucket)
        reg.gauge("serve.num_trees").set(len(self._model.trees))
        reg.gauge("serve.model_version").set(self._model.version)
        reg.gauge("serve.health").set(_HEALTH_CODE["ready"])
        if warmup:
            self.warmup()

    @staticmethod
    def _load_booster(model, params: Optional[Dict]):
        from ..basic import Booster
        if isinstance(model, Booster):
            booster = model
            if params:
                booster.config = Config.from_params(
                    dict(booster.params, **params))
        else:
            path = str(model)
            # serve_* knobs ride in as Booster params; the loader's
            # apply_model_header merges the file's metadata (objective,
            # sigmoid, num_class) on top and rebuilds the Config once
            booster = Booster(params=dict(params or {}))
            # one format dispatcher: .proto / .json / text all resolve
            # inside load_model_file
            from ..io.model_text import load_model_file
            load_model_file(booster, path)
        booster._ensure_finalized()
        return booster

    # -------------------------------------------------- model-state access

    def model_snapshot(self) -> _ModelState:
        """The current model state, read once — callers that span several
        internal calls (the micro-batcher worker, verification) hold the
        SAME snapshot across all of them so a concurrent ``reload`` can
        never mix versions inside one request."""
        return self._model

    @property
    def booster(self):
        return self._model.booster

    @property
    def num_class_models(self) -> int:
        return self._model.num_class_models

    @property
    def num_iteration(self) -> int:
        return self._model.num_iteration

    @property
    def num_features(self) -> int:
        return self._model.num_features

    @property
    def has_categorical(self) -> bool:
        return self._model.has_categorical

    @property
    def model_version(self) -> int:
        return self._model.version

    @property
    def _trees(self):
        return self._model.trees

    @property
    def _forests(self):
        return self._model.forests

    # ------------------------------------------------------------- compile

    def jit_entrypoints(self):
        """(name, jitted callable) pairs for RecompileGuard registration
        — the CURRENT model's walk (re-register after a reload)."""
        m = self._model
        return [] if m.walk is None else [("serve.forest_walk", m.walk)]

    def warmup(self) -> int:
        """AOT-compile the forest walk for every (class, bucket) signature
        so the first real request — and every one after — dispatches a
        warm executable. Returns the number of signatures compiled. With
        the persistent compile cache enabled this replays from disk on
        restart. Captures a cost report per bucket when cost analysis is
        on (``cost.serve.forest_walk.b<N>.*`` gauges)."""
        return self._warm_state(self._model)

    def _warm_state(self, m: _ModelState) -> int:
        if m.walk is None or m.warmed:
            return 0
        from ..observability import costs as obs_costs
        n = 0
        with obs.span("serve.warmup", buckets=len(self.buckets),
                      model_version=m.version):
            for k, f in enumerate(m.forests):
                for B in self.buckets:
                    codes = np.zeros((B, m.num_features), np.int32)
                    mask = np.zeros((B, m.num_features), bool)
                    args = (*m.dev[k], codes, mask, mask)
                    if obs_costs.enabled():
                        obs_costs.capture_jit(
                            f"serve.forest_walk.b{B}", m.walk, args,
                            dims=dict(rows=B, trees=f.num_trees),
                            fingerprint=(k, B, m.num_features,
                                         f.num_trees, int(f.max_leaves)))
                    # the call compiles synchronously; the async result is
                    # deliberately dropped — warmup needs the executable,
                    # not the value
                    m.walk(*args)
                    n += 1
                    obs.inc("serve.bucket_compiles")
        m.warmed = True
        return n

    # ------------------------------------------------------------ dispatch

    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket holding ``n`` rows (requests beyond the
        top bucket are chunked by the caller)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_bucket

    def _dispatch(self, m: _ModelState, k: int, codes: np.ndarray,
                  is_nan: np.ndarray, is_zero: np.ndarray,
                  record: bool = True) -> np.ndarray:
        """One device dispatch of <= max_bucket rows for class ``k``,
        padded to the bucket: returns leaf indices [n, T]. A failure of
        the walk itself surfaces as ``DeviceDispatchError`` after landing
        on the circuit breaker (``record=False`` — probe / reload
        verification — keeps injected or candidate failures off the live
        breaker's books)."""
        n = codes.shape[0]
        B = self.bucket_for(n)
        if n < B:
            pad = B - n
            codes = np.concatenate(
                [codes, np.zeros((pad, codes.shape[1]), codes.dtype)])
            is_nan = np.concatenate(
                [is_nan, np.zeros((pad, is_nan.shape[1]), bool)])
            is_zero = np.concatenate(
                [is_zero, np.zeros((pad, is_zero.shape[1]), bool)])
        t0 = obs.clock()
        reg = obs.get_registry()
        try:
            if self.chaos is not None:
                self.chaos()
            # the contractual result sync: ONE device->host fetch per
            # dispatch (tpu-lint R011 baseline-exempt; everything else in
            # serving/ stays sync-free)
            leaves = np.asarray(m.walk(*m.dev[k], codes, is_nan, is_zero))
        except Exception as e:                                # noqa: BLE001
            if record:
                self._on_dispatch_failure(e)
            raise DeviceDispatchError(
                f"device forest walk failed for bucket {B}: "
                f"{type(e).__name__}: {e}") from e
        if record:
            self._breaker.record_success()
            reg.summary("serve.dispatch_ms").observe((obs.clock() - t0) * 1e3)
            reg.histogram("serve.batch_fill_frac").observe(n / B)
            reg.counter(f"serve.bucket.{B}").inc()
        return leaves[:n]

    # --------------------------------------------- degrade / probe / health

    def _on_dispatch_failure(self, err: BaseException) -> None:
        Log.warning("serve: device dispatch failed (%s: %s) — serving this "
                    "request via the host predictor",
                    type(err).__name__, err)
        if self._breaker.record_failure(err):
            Log.warning(
                "serve: circuit breaker OPEN after %d failure(s) in %.1fs — "
                "engine is DEGRADED (host predictor, bit-identical answers "
                "at host throughput) until the device probe succeeds",
                self._breaker.failures, self._breaker.window_s)
            obs.get_registry().gauge("serve.health").set(
                _HEALTH_CODE["degraded"])
            self._start_probe()

    def _start_probe(self) -> None:
        # _probe_running (not Thread.is_alive) gates the start: the probe
        # thread clears it under the same lock as its exit decision, so a
        # breaker re-trip can never observe a probe that has already
        # decided to die and skip starting a fresh one
        with self._probe_lock:
            if self._probe_running or self._closed:
                return
            self._probe_running = True
            self._probe_stop.clear()
            self._probe_thread = threading.Thread(
                target=self._probe_loop, name="lgbm-serve-probe", daemon=True)
            self._probe_thread.start()

    def _probe_loop(self) -> None:
        """Background device re-warm: while the breaker is open, try one
        real (smallest-bucket) dispatch every ``serve_probe_interval_s``;
        the first success closes the breaker and restores ``ready``."""
        interval = self.config.serve_probe_interval_s
        while True:
            stopped = self._probe_stop.wait(interval)
            if not stopped and not self._closed and self._breaker.is_open:
                try:
                    self._probe_once()
                except Exception as e:                        # noqa: BLE001
                    obs.inc("serve.probe_failures")
                    Log.debug("serve: device probe failed (%s: %s) — still "
                              "degraded", type(e).__name__, e)
                    continue
                self._breaker.reset()
                obs.get_registry().gauge("serve.health").set(
                    _HEALTH_CODE["ready"])
                Log.warning("serve: device probe succeeded — circuit "
                            "breaker closed, engine READY on the device "
                            "path again")
            # exit decision, atomic with _start_probe: a re-trip lands
            # either before this check (breaker open again -> keep
            # probing) or after _probe_running clears (-> fresh thread)
            with self._probe_lock:
                if stopped or self._closed or not self._breaker.is_open:
                    self._probe_running = False
                    return

    def _probe_once(self) -> None:
        m = self._model
        if m.walk is None:
            return
        B = self.buckets[0]
        codes = np.zeros((B, m.num_features), np.int32)
        mask = np.zeros((B, m.num_features), bool)
        self._dispatch(m, 0, codes, mask, mask, record=False)

    def health(self) -> str:
        """``ready`` | ``degraded`` | ``down`` — the load-balancer probe.
        ``degraded`` = the circuit breaker is open and requests serve
        via the host predictor (correct, slower); ``down`` = the engine
        was closed and admits nothing."""
        if self._closed:
            return "down"
        if self._breaker.is_open:
            return "degraded"
        return "ready"

    def close(self) -> None:
        """Stop the probe thread and refuse further requests
        (``health()`` -> ``down``). Idempotent."""
        # flags flip under _probe_lock so a concurrent _start_probe either
        # ran first (then t below is its thread and gets joined) or sees
        # _closed and refuses — it can never re-clear _probe_stop after us.
        # The join happens OUTSIDE the lock: the probe's exit decision
        # needs the same lock.
        with self._probe_lock:
            self._closed = True
            self._probe_stop.set()
            t = self._probe_thread
            self._probe_thread = None
        if t is not None:
            t.join(timeout=5.0)
        # after the join, no probe thread survives to overwrite this
        obs.get_registry().gauge("serve.health").set(_HEALTH_CODE["down"])

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---------------------------------------------------------- hot reload

    def reload(self, model, params: Optional[Dict] = None,
               num_iteration: Optional[int] = None,
               verify_rows: int = 256) -> int:
        """Hot-swap the served model with verified rollback.

        The candidate is built and AOT-compiled OFF TO THE SIDE (the live
        model keeps serving), verified **bit-identical** against its own
        booster's host ``predict()`` on a held sample of ``verify_rows``
        rows (NaN/zero cells included), then swapped in atomically —
        requests hold a state snapshot, so in-flight batches finish on
        the old forest and every response matches exactly one model
        version. ANY failure (shape mismatch, compile error, verification
        mismatch) rolls back: the old model is still serving when the
        raised ``ReloadError`` reaches the caller. Returns the new
        model version. Counters: ``serve.reloads`` /
        ``serve.reload_rollbacks``."""
        if self._closed:
            raise ServingClosedError("reload() on a closed ServingEngine")
        with self._reload_lock:
            old = self._model
            try:
                booster = self._load_booster(model, params)
                cand = _ModelState(booster, num_iteration,
                                   version=old.version + 1)
                if cand.num_features != old.num_features:
                    raise ReloadError(
                        f"candidate expects {cand.num_features} features, "
                        f"live model serves {old.num_features} — a reload "
                        f"must stay request-compatible")
                if cand.num_class_models != old.num_class_models:
                    raise ReloadError(
                        f"candidate has {cand.num_class_models} class "
                        f"model(s), live model {old.num_class_models} — "
                        f"the response shape would change under callers")
                self._warm_state(cand)
                self._verify_state(cand, verify_rows)
            except Exception as e:
                obs.inc("serve.reload_rollbacks")
                Log.warning("serve: reload ROLLED BACK (still serving "
                            "model_version=%d): %s: %s",
                            old.version, type(e).__name__, e)
                if isinstance(e, ReloadError):
                    raise
                raise ReloadError(f"reload failed and rolled back: "
                                  f"{type(e).__name__}: {e}") from e
            # atomic swap: a plain attribute rebind — concurrent requests
            # already hold their snapshot and finish on the old forest
            self._model = cand
            obs.inc("serve.reloads")
            reg = obs.get_registry()
            reg.gauge("serve.model_version").set(cand.version)
            reg.gauge("serve.num_trees").set(len(cand.trees))
            Log.info("serve: hot reload -> model_version=%d (%d trees, "
                     "verified bit-identical on %d rows)",
                     cand.version, len(cand.trees), verify_rows)
            return cand.version

    def _verify_state(self, m: _ModelState, verify_rows: int) -> None:
        """Bit-identity gate: the candidate's DEVICE path (no fallback, no
        breaker accounting) must reproduce its own booster's host
        ``predict()`` exactly on a held sample with NaN and zero cells —
        the same contract ``bench.py --serve`` pins for the live path."""
        if verify_rows <= 0:
            return
        rng = np.random.RandomState(0x5EED)
        X = np.asarray(rng.randn(verify_rows, m.num_features) * 2.0,
                       np.float64)
        X[rng.rand(verify_rows, m.num_features) < 0.05] = np.nan
        X[rng.rand(verify_rows, m.num_features) < 0.05] = 0.0
        want = m.booster.predict(X)
        raw = self._predict_raw_for(m, X, allow_fallback=False, record=False)
        got = self._finish_for(m, raw, raw_score=False)
        if not np.array_equal(want, got, equal_nan=True):
            # both sides are host float64 numpy already (booster.predict /
            # _finish_for) — no materialization needed for the diagnostic
            diff = float(np.max(np.abs(np.nan_to_num(want)
                                       - np.nan_to_num(got))))
            raise ReloadError(
                f"candidate verification FAILED: device path differs from "
                f"its own Booster.predict on {verify_rows} held rows "
                f"(max abs diff {diff:g})")

    # ----------------------------------------------------------- prediction

    def _predict_host(self, m: _ModelState, X: np.ndarray,
                      record: bool = True, degraded: bool = False
                      ) -> np.ndarray:
        """Host predictor path: per-tree f64 accumulation in tree order —
        the categorical route and the circuit-breaker fallback (identical
        numbers to the device path by the bit-identity contract)."""
        K = m.num_class_models
        raw = np.zeros((K, X.shape[0]), np.float64)
        for i, t in enumerate(m.trees):
            raw[i % K] += t.predict(X)
        if record:
            obs.get_registry().counter("serve.rows").inc(X.shape[0])
            if degraded:
                obs.inc("serve.host_fallback")
        return raw

    def _predict_raw_for(self, m: _ModelState, X: np.ndarray,
                         deadline: Optional[float] = None,
                         allow_fallback: bool = True,
                         record: bool = True) -> np.ndarray:
        """Raw scores [K, N] f64 for a prepared f64 matrix — traversal on
        device (bucketed), leaf accumulation on host in f64 tree order
        (bit-identical to the host predictor). Degraded state or a
        device-dispatch failure reroutes the WHOLE request to the host
        predictor (same numbers); ``allow_fallback=False`` (verification)
        lets the failure surface instead."""
        N = X.shape[0]
        K = m.num_class_models
        if m.has_categorical or (allow_fallback and self._breaker.is_open):
            return self._predict_host(
                m, X, record=record, degraded=not m.has_categorical)
        raw = np.zeros((K, N), np.float64)
        try:
            for k, forest in enumerate(m.forests):
                if forest.num_trees == 0:
                    continue
                codes, is_nan, is_zero = forest.encode_rows(X)
                lv = None if forest.has_linear else forest.leaf_value64
                lo = 0
                while lo < N:
                    if deadline is not None and obs.clock() > deadline:
                        obs.inc("serve.deadline_exceeded")
                        raise DeadlineExceededError(
                            f"deadline passed after {lo} of {N} rows — "
                            f"dropping the dispatch")
                    n = min(N - lo, self.max_bucket)
                    leaves = self._dispatch(
                        m, k, codes[lo:lo + n], is_nan[lo:lo + n],
                        is_zero[lo:lo + n], record=record)
                    # sequential f64 accumulation in tree order — the exact
                    # operation order of Booster.predict's host loop.
                    # Linear-leaf forests route each tree's leaf indices
                    # through Tree.leaf_outputs (the ONE home of host
                    # linear evaluation): device traversal stays integer-
                    # exact, the dot product runs host f64, and served
                    # bits equal Booster.predict's
                    out = raw[k]
                    if forest.has_linear:
                        Xc = X[lo:lo + n]
                        for t, tr in enumerate(forest._trees):
                            out[lo:lo + n] += tr.leaf_outputs(
                                Xc, leaves[:, t])
                    else:
                        for t in range(forest.num_trees):
                            out[lo:lo + n] += lv[t, leaves[:, t]]
                    lo += n
        except DeviceDispatchError:
            if not allow_fallback:
                raise
            # graceful degradation: the device path failed mid-request;
            # the host predictor serves the same bits at host throughput
            return self._predict_host(m, X, record=record, degraded=True)
        if record:
            obs.get_registry().counter("serve.rows").inc(N)
        return raw

    def _finish_for(self, m: _ModelState, raw: np.ndarray,
                    raw_score: bool) -> np.ndarray:
        """Output transform — Booster.predict's tail, verbatim semantics."""
        K = m.num_class_models
        if m.config.boosting_normalized == "rf":
            raw = raw / max(len(m.trees) // K, 1)
        elif not raw_score:
            raw = m.booster._convert_output(raw)
        return raw[0] if K == 1 else raw.T

    # back-compat single-model entry points (hold one snapshot internally)
    def _predict_raw(self, X: np.ndarray) -> np.ndarray:
        return self._predict_raw_for(self._model, X)

    def _finish(self, raw: np.ndarray, raw_score: bool) -> np.ndarray:
        return self._finish_for(self._model, raw, raw_score)

    def predict(self, X, raw_score: bool = False,
                deadline_ms: Optional[float] = None) -> np.ndarray:
        """Serve one request: [N, F] (or a single row) -> predictions,
        bit-identical to ``Booster.predict`` on the same rows.
        ``deadline_ms`` (default ``serve_deadline_ms``; 0 = none) bounds
        the request — between chunk dispatches an expired deadline raises
        ``DeadlineExceededError`` instead of wasting further device
        time."""
        if self._closed:
            raise ServingClosedError("predict() on a closed ServingEngine")
        t0 = obs.clock()
        m = self._model
        dl = self.config.serve_deadline_ms if deadline_ms is None \
            else deadline_ms
        deadline = (t0 + dl / 1e3) if dl and dl > 0 else None
        X = self._as_matrix(X, m)
        out = self._finish_for(
            m, self._predict_raw_for(m, X, deadline=deadline), raw_score)
        reg = obs.get_registry()
        reg.counter("serve.requests").inc()
        reg.summary("serve.latency_ms").observe((obs.clock() - t0) * 1e3)
        return out

    def _as_matrix(self, X, m: Optional[_ModelState] = None) -> np.ndarray:
        # host input normalization (caller data, not a device value)
        m = m or self._model
        mat = np.asarray(X, np.float64)
        if mat.ndim == 1:
            mat = mat.reshape(1, -1)
        if mat.shape[1] != m.num_features:
            raise ValueError(
                f"request has {mat.shape[1]} features, model expects "
                f"{m.num_features}")
        return mat

    def describe(self) -> Dict:
        m = self._model
        return {"buckets": list(self.buckets),
                "num_trees": len(m.trees),
                "num_class_models": m.num_class_models,
                "num_features": m.num_features,
                "categorical_host_path": m.has_categorical,
                "warmed": m.warmed,
                "model_version": m.version,
                "health": self.health(),
                "breaker": self._breaker.state}
