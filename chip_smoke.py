"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls (``lgb.train`` -> ``Booster.predict`` -> ``ServingEngine``), at the
full width of the repo's headline model: the HIGGS configuration, 10.5M rows
x 28 f32 features, 255 leaves, 255 bins, ``tree_batch=4``, every ``tpu_*``
option at its default. Depth is cut to 12 iterations — three fused
dispatches, because the first TWO each compile the step (the second call
sees committed inputs and jit compiles a second variant) and only the third
shows the steady state. The data is synthetic, made from a seed
(``bench._higgs_like``).

Legs, in order — any failed check raises, and the process exits non-zero:

  (a) device   refuse to start unless jax's default backend is a TPU
  (b) train    residency=device, device ingest engaged with ONE compile,
               every tree split, finite predictions, held-out AUC floor
  (c) pallas   at the headline kernel shape class, both histogram kernels
               inside jit match f64 sums (tests/test_pallas_hist.py
               tolerances), the Pallas one compiled by Mosaic (not
               interpret); then tpu_hist_kernel=mixed grows the same trees
               as xla
  (d) serve    protobuf round trip -> ServingEngine.warmup() -> requests of
               1/100/4096 rows bit-identical to Booster.predict, health
               ready, zero host fallbacks
  (e) multichip  with >= 4 chips: tree_learner=data over 4 devices, shards
               on 4 distinct devices, predictions within
               tests/test_parallel.py's tolerance of the serial booster;
               with fewer it prints SKIPPED and does not count as passed

Run it from the root of a checkout:  python chip_smoke.py
It imports the package from that checkout (no .pth, no install), needs no
network, starts no other process, and places the compile cache through
``utils/cache.resolve_compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``). The LAST stdout line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import collections
import importlib.metadata
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

ROWS = 10_500_000
HOLDOUT = 500_000
ITERS = 12
PARAMS = dict(objective="binary", num_leaves=255, max_bin=255,
              learning_rate=0.1, min_data_in_leaf=100, tree_batch=4,
              metric="none", verbose=1)
# 8 trees at lr=0.1 reached 0.936 on this generator (my chip run, PR 21); a
# degenerate or mis-routed forest sits near 0.5
AUC_FLOOR = 0.90
# the Pallas leg trains two boosters; the kernel shape class (F=28, B=256,
# S=25, 5 channels, 512-row grid step) does not depend on the row count
PALLAS_ROWS = 300_000
PALLAS_ITERS = 4
SERVE_REQUESTS = (1, 100, 4096)
MULTICHIP_DEVICES = 4

_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileMeter:
    """Seconds jax spent in backend compiles (a persistent-cache hit counts
    only its retrieval) plus the cache's own hit/miss counters — read from
    jax.monitoring, so it sees every program the process compiles."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **kwargs):
        self.events[event.rsplit("/", 1)[-1]] += 1

    def mark(self):
        return (self.seconds, self.events["cache_hits"],
                self.events["cache_misses"])

    def since(self, mark=(0.0, 0, 0)) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 2),
                "cache_hits": self.events["cache_hits"] - mark[1],
                "cache_misses": self.events["cache_misses"] - mark[2]}


class DispatchClock:
    """lgb.train callback: wall-clock of each fused dispatch, ended by a
    block on the training scores (dispatch is asynchronous), and the
    compile seconds that fell inside it. The first interval also holds the
    set-up before the first dispatch (binning sample, ingest)."""

    def __init__(self, meter: CompileMeter):
        self.meter = meter
        self.last = time.perf_counter()
        self.mark = meter.mark()
        self.seconds = []
        self.compile_seconds = []

    def __call__(self, env):
        import jax
        jax.block_until_ready(env.model._gbdt.score)
        now = time.perf_counter()
        self.seconds.append(round(now - self.last, 2))
        self.compile_seconds.append(self.meter.since(self.mark)["compile_s"])
        self.last, self.mark = now, self.meter.mark()


# ------------------------------------------------------------------ leg (a)

def device_leg() -> dict:
    import jax
    import jaxlib
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: jax found no accelerator (default backend "
            f"{backend!r}) — this check only means something on a TPU")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    say(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return device


# ------------------------------------------------------------------ leg (b)

def train_leg(meter: CompileMeter, X, y, Xt, yt, params=PARAMS,
              iters=ITERS) -> dict:
    import lightgbm_tpu as lgb
    from bench import _auc
    from lightgbm_tpu.observability import memory as obs_memory

    mark = meter.mark()
    clock = DispatchClock(meter)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=iters,
                    keep_training_booster=True, verbose_eval=False,
                    callbacks=[clock])
    g = bst._gbdt
    compiles = meter.since(mark)
    tb = g.tree_batch
    steady = clock.seconds[-1] / tb
    say(f"train: residency={g.residency} hist_kernel={g.spec.hist_kernel} "
        f"tree_batch={tb} rows={g.num_data}")
    say(f"train: dispatch seconds {clock.seconds} (the first holds the "
        f"set-up) of which compile {clock.compile_seconds}; compile total "
        f"{compiles['compile_s']}s, cache hits {compiles['cache_hits']}, "
        f"misses {compiles['cache_misses']}")
    check(clock.compile_seconds[-1] < 1.0,
          f"the last dispatch still compiled "
          f"({clock.compile_seconds[-1]}s) — no steady state to report")
    say(f"train: steady {steady:.2f}s/iter = "
        f"{g.num_data / steady / 1e6:.2f} Mrow-tree/s (last dispatch)")
    check(g.residency == "device",
          f"residency resolved to {g.residency!r}: the headline needs "
          f"~1.4 of 16 GB, so the host-driven stream path here is a failure")
    rep = g._ingest_report
    check(rep is not None, "device ingest did not engage (host binning ran)")
    check(rep["compiles"] == 1,
          f"device ingest compiled {rep['compiles']} executables, not 1")
    say(f"train: device ingest {rep['rows']} rows in {rep['seconds']:.1f}s "
        f"({rep['n_chunks']} chunks, {rep['stalls']} stalls, compiles=1)")
    check(len(clock.seconds) == iters // tb and len(bst.trees) == iters,
          f"{len(clock.seconds)} dispatches / {len(bst.trees)} trees for "
          f"{iters} iterations at tree_batch={tb}")
    leaves = [int(t.num_leaves) for t in bst.trees]
    check(min(leaves) > 1, f"a tree did not split: leaves per tree {leaves}")

    est = obs_memory.hbm_preflight(g)["total_bytes"]
    peak = obs_memory.device_memory().get("peak_bytes")
    check(peak is not None, "the device reports no memory statistics")
    say(f"train: HBM peak {peak / 2**30:.2f} GB vs pre-flight estimate "
        f"{est / 2**30:.2f} GB")

    t0 = time.perf_counter()
    pred = bst.predict(Xt)
    predict_s = time.perf_counter() - t0
    check(pred.shape == (Xt.shape[0],) and bool(np.isfinite(pred).all()),
          "Booster.predict returned non-finite or mis-shaped predictions")
    auc = _auc(yt, pred)
    say(f"train: predict {Xt.shape[0]} held-out rows in {predict_s:.1f}s "
        f"(device forest walk), AUC {auc:.4f} (floor {AUC_FLOOR}), leaves "
        f"per tree {leaves}")
    check(auc > AUC_FLOOR, f"held-out AUC {auc:.4f} <= floor {AUC_FLOOR}")
    return {"booster": bst, "pred": pred,
            "report": {"residency": g.residency,
                       "hist_kernel": g.spec.hist_kernel,
                       "dispatch_s": clock.seconds,
                       "dispatch_compile_s": clock.compile_seconds,
                       "steady_s_per_iter": round(steady, 3),
                       "ingest_s": rep["seconds"], "auc": round(auc, 6),
                       "hbm_peak_bytes": int(peak),
                       "hbm_preflight_bytes": int(est), **compiles}}


# ------------------------------------------------------------------ leg (c)

def pallas_leg(X, y, Xt, rows=PALLAS_ROWS, iters=PALLAS_ITERS,
               params=PARAMS) -> dict:
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.grower import _slot_grouped_rows
    from lightgbm_tpu.ops.histogram import build_histograms, pack_rows

    check(ph._INTERPRET is False,
          "pallas kernel is in interpret mode — Mosaic would not compile it")
    # Both kernels alone, INSIDE jit as the train step runs them, at the
    # headline shape class, full and compacted pass. Each must match f64
    # sums (this is what pins the bf16 hi/lo weight split on the chip: the
    # TPU compiler may elide an f32->bf16->f32 round trip that the CPU
    # backend keeps, see ops/histogram._split_hi_lo), the two must agree,
    # each kernel's compacted pass must agree with the STREAMED xla pass
    # over the same pending leaves (the path with no row index, no packed
    # rows and no gather), and the compiled Pallas program must hold a
    # Mosaic custom call.
    rng = np.random.RandomState(0)
    n, f, bins, slots = 65536, X.shape[1], 256, 25
    codes_np = rng.randint(0, 255, size=(n, f)).astype(np.uint8)
    grad_np = rng.randn(n).astype(np.float32)
    hess_np = np.abs(rng.randn(n)).astype(np.float32)
    leaf_np = rng.randint(0, 100, size=n).astype(np.int32)
    pending = leaf_np < slots                    # leaf l -> slot l, l < slots
    ref64 = np.zeros((slots, f, bins, 3))
    for j in range(f):
        flat = leaf_np[pending].astype(np.int64) * bins + codes_np[pending, j]
        for c, w in enumerate((grad_np, hess_np, np.ones(n))):
            ref64[:, j, :, c] = np.bincount(
                flat, weights=w[pending].astype(np.float64),
                minlength=slots * bins).reshape(slots, bins)
    slot_of_leaf = jnp.full(256, -1, jnp.int32).at[jnp.arange(slots)].set(
        jnp.arange(slots))
    args = (jnp.asarray(codes_np), jnp.asarray(grad_np), jnp.asarray(hess_np),
            jnp.ones(n, jnp.float32), jnp.asarray(leaf_np), slot_of_leaf)
    # a compacted pass as the grower hands it over: the rows grouped by
    # pending slot by its one sort, the rows a slot, the packed rows
    row_idx, counts = _slot_grouped_rows(slot_of_leaf[args[4]], slots)
    compacted = dict(row_idx=row_idx, n_active=jnp.sum(counts),
                     slot_counts=counts)
    args += (pack_rows(*args[:4], exact=False)[0],)
    static = dict(num_slots=slots, num_bins_padded=bins, chunk_rows=512)
    worst = 0.0
    streamed = None
    for name, kw in (("full", {}), ("compacted", compacted)):
        outs = {}
        for kernel, build in (("xla", build_histograms),
                              ("pallas", ph.build_histograms_pallas)):
            compiled = jax.jit(lambda *a, build=build, kw=kw: build(
                *a[:-1], packed=a[-1], **static, **kw)).lower(*args).compile()
            if kernel == "pallas":
                check("tpu_custom_call" in compiled.as_text(),
                      f"no Mosaic custom call in the compiled {name} pass")
            outs[kernel] = np.asarray(compiled(*args))
            err = float(np.max(np.abs(outs[kernel][..., :2] - ref64[..., :2])))
            worst = max(worst, err)
            check(err < 5e-3, f"{kernel} {name} pass: grad/hess sums are "
                              f"{err:.3g} off the f64 sums (bf16 lo lost?)")
            np.testing.assert_array_equal(outs[kernel][..., 2], ref64[..., 2])
        np.testing.assert_allclose(outs["pallas"], outs["xla"],
                                   rtol=1e-5, atol=1e-4)
        if streamed is None:
            streamed = outs["xla"]
        for kernel in outs:
            np.testing.assert_allclose(outs[kernel], streamed,
                                       rtol=1e-5, atol=1e-4)
        say(f"pallas: {name} pass at F={f} B={bins} S={slots} chunk=512: "
            f"Mosaic-compiled kernel matches xla, both match f64 sums and "
            f"the streamed xla pass")

    # end to end: the mixed dispatch grows the same trees as xla
    preds = {}
    for kernel in ("xla", "mixed"):
        bst = lgb.train(dict(params, tpu_hist_kernel=kernel),
                        lgb.Dataset(X[:rows], label=y[:rows]),
                        num_boost_round=iters, keep_training_booster=True,
                        verbose_eval=False)
        check(bst._gbdt.spec.hist_kernel == kernel,
              f"asked for {kernel}, got {bst._gbdt.spec.hist_kernel}")
        preds[kernel] = bst.predict(Xt)
    np.testing.assert_allclose(preds["mixed"], preds["xla"],
                               rtol=1e-4, atol=1e-5)
    diff = float(np.max(np.abs(preds["mixed"] - preds["xla"])))
    say(f"pallas: mixed vs xla over {iters} iterations at {rows} rows: max "
        f"abs prediction diff {diff:.3g}")
    return {"interpret": False, "max_abs_err_vs_f64": worst,
            "max_abs_diff": diff}


# ------------------------------------------------------------------ leg (d)

def serve_leg(meter: CompileMeter, bst, Xt) -> dict:
    from lightgbm_tpu import observability as obs
    from lightgbm_tpu.serving import ServingEngine

    mark = meter.mark()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "model.proto")
        bst.save_model(path)
        engine = ServingEngine(path, warmup=False)   # loads the file now
    with engine:
        t0 = time.perf_counter()
        n_sig = engine.warmup()
        warm_s = time.perf_counter() - t0
        latencies = {}
        for n in SERVE_REQUESTS:
            req = np.asarray(Xt[:n], np.float64)
            t0 = time.perf_counter()
            got = engine.predict(req)
            latencies[n] = round((time.perf_counter() - t0) * 1e3, 2)
            check(np.array_equal(got, bst.predict(req)),
                  f"served {n}-row request differs from Booster.predict")
        health = engine.health()
        fallbacks = obs.snapshot()["counters"].get("serve.host_fallback", 0)
    say(f"serve: warmup {n_sig} signatures in {warm_s:.1f}s, requests "
        f"{list(SERVE_REQUESTS)} bit-identical, latency ms {latencies}, "
        f"health={health}, host_fallback={fallbacks}")
    check(health == "ready", f"ServingEngine.health() == {health!r}")
    check(fallbacks == 0, f"{fallbacks} request(s) answered from the host")
    return {"warmup_s": round(warm_s, 2), "latency_ms": latencies,
            **meter.since(mark)}


# ------------------------------------------------------------------ leg (e)

def multichip_leg(meter: CompileMeter, X, y, Xt, serial_pred, params=PARAMS,
                  iters=ITERS, n_devices=MULTICHIP_DEVICES):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import memory as obs_memory

    have = jax.device_count()
    if have < n_devices:
        say(f"multichip: SKIPPED ({have} device(s))")
        return None
    clock = DispatchClock(meter)
    bst = lgb.train(dict(params, tree_learner="data", num_machines=n_devices),
                    lgb.Dataset(X, label=y), num_boost_round=iters,
                    keep_training_booster=True, verbose_eval=False,
                    callbacks=[clock])
    g = bst._gbdt
    check(g.pctx.num_devices == n_devices,
          f"mesh resolved to {g.pctx.num_devices} device(s), not {n_devices}")
    shard_devices = sorted(str(s.device) for s in g.Xb.addressable_shards)
    check(len(set(shard_devices)) == n_devices,
          f"row shards sit on {shard_devices}")
    peaks = [round(obs_memory.device_memory(d)["peak_bytes"] / 2**30, 2)
             for d in g.pctx.devices]
    pred = bst.predict(Xt)
    diff = float(np.max(np.abs(pred - serial_pred)))
    steady = clock.seconds[-1] / g.tree_batch
    say(f"multichip: tree_learner=data over {n_devices} devices, shards on "
        f"{shard_devices}, dispatch seconds {clock.seconds} of which "
        f"compile {clock.compile_seconds}, steady {steady:.2f}s/iter, "
        f"per-device HBM peak GB {peaks}, max abs prediction diff vs serial "
        f"{diff:.3g}")
    np.testing.assert_allclose(pred, serial_pred, rtol=1e-4, atol=1e-4)
    return {"num_devices": n_devices, "shard_devices": shard_devices,
            "dispatch_s": clock.seconds,
            "dispatch_compile_s": clock.compile_seconds,
            "steady_s_per_iter": round(steady, 3), "hbm_peak_gb": peaks,
            "max_abs_diff_vs_serial": diff}


# --------------------------------------------------------------------- main

def main() -> int:
    device = device_leg()
    from bench import _higgs_like
    from lightgbm_tpu.utils.cache import resolve_compile_cache
    cache_dir = resolve_compile_cache()
    say(f"compile cache: {cache_dir}")
    meter = CompileMeter()

    X, y = _higgs_like(ROWS + HOLDOUT)
    Xt, yt = X[ROWS:], y[ROWS:]
    X, y = X[:ROWS], y[:ROWS]
    say(f"data: {ROWS} x {X.shape[1]} f32 train rows, {HOLDOUT} held out")

    report = {"device": device, "compile_cache_dir": cache_dir}
    trained = train_leg(meter, X, y, Xt, yt)
    report["train"] = trained["report"]
    report["pallas"] = pallas_leg(X, y, Xt)
    report["serve"] = serve_leg(meter, trained["booster"], Xt)
    report["multichip"] = multichip_leg(meter, X, y, Xt, trained["pred"])
    report["total"] = {"wall_s": round(time.perf_counter() - _T0, 1),
                       **meter.since()}
    say("report: " + json.dumps(report))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
