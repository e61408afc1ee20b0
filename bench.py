"""Benchmark harness: Higgs-config training throughput + accuracy on one TPU.

Reference workload (BASELINE.md / docs/Experiments.rst:106): LightGBM CPU
trains HIGGS (10.5M rows x 28 features) for 500 iterations with
num_leaves=255, max_bin=255, lr=0.1 in 238.505 s on 2x E5-2670v3 =>
10.5e6 * 500 / 238.505 = 22.0 Mrow-tree/s, AUC 0.845154
(docs/Experiments.rst:127).

This harness:
- trains the REAL scale: 10.5M rows x 28 features, synthetic HIGGS-like
  with learnable nonlinear structure (histogram cost depends on shape, not
  values; accuracy is gated by a parity check, not an absolute target);
- measures steady-state wall-clock per boosting iteration on-device;
- reports AUC on a held-out split alongside throughput — a throughput
  number with no quality check can be satisfied by degenerate trees;
- gates accuracy by WAVE-vs-EXACT parity (tpu_wave_size=1 is the
  reference-ordering mode; the analog of the reference's GPU-parity table,
  docs/GPU-Performance.rst:135-159), run at reduced scale to fit budget.

The headline needs the chip: with no TPU, or when the headline phase
fails, the process exits non-zero and prints no result — there is no CPU
stand-in and no replay of an older measurement. One process holds the chip
for the whole run; no phase starts a child that needs it. Optional phases
after the headline check the remaining watchdog budget and are skipped
(reported as null / an *_error field) when it runs out.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "auc": ...}
"""
import json
import os
import signal
import subprocess
import sys
import time
import traceback
from contextlib import contextmanager

import numpy as np

BASELINE_MROW_TREE_PER_S = 10.5e6 * 500 / 238.505 / 1e6   # 22.0
# MS-LTR: 2,270,296 rows x 137 features, 500 iters in 215.32 s
# (docs/Experiments.rst:21,110), NDCG@10 0.527371 (:143)
RANK_BASELINE_MROW_TREE_PER_S = 2_270_296 * 500 / 215.320316 / 1e6   # 5.27


class BenchTimeout(Exception):
    pass


class PhaseTimeout(Exception):
    """One OPTIONAL phase exceeded its private watchdog subdeadline —
    caught at the phase boundary so the JSON degrades (an *_error field)
    instead of the whole-run alarm voiding the headline."""


@contextmanager
def _phase_watchdog(name, seconds):
    """Hard per-phase subdeadline on top of the global SIGALRM watchdog.

    Pauses the global alarm, re-arms SIGALRM to min(phase budget, what the
    global budget has left minus a margin) with a handler that raises
    PhaseTimeout, and on exit restores the global alarm minus the time the
    phase consumed — the whole-run BenchTimeout contract is unchanged. A
    native call that never returns is not interruptible (SIGALRM fires
    between bytecodes); this guard bounds everything interruptible."""
    remaining = signal.alarm(0)               # pause the global watchdog
    if remaining:
        budget = int(max(1, min(seconds, remaining - 10)))
    else:
        budget = int(max(1, seconds))
    prev = signal.getsignal(signal.SIGALRM)

    def on_alarm(signum, frame):
        raise PhaseTimeout(
            f"phase {name!r} exceeded its {budget}s watchdog subdeadline")

    t0 = time.time()
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
        if remaining:
            signal.alarm(max(1, int(remaining - (time.time() - t0))))


def _round_tp(x: float) -> float:
    """1 decimal for real throughputs, 4 for sub-1 values (the CPU-only
    correctness modes' 0.003 Mrow-tree/s must not print as 0.0)."""
    return round(x, 1) if x >= 1 else round(x, 4)


def _round_ratio(x: float) -> float:
    """3 decimals normally, 6 for tiny ratios."""
    return round(x, 3) if x >= 0.01 else round(x, 6)


# headline result snapshot, reported even if a later optional phase times out
_PARTIAL = {}


def _timed_update_phase(name, bst, warmup, timed, timings, tree_batch=1):
    """Warm up + time one booster's training loop with the attributable
    per-phase breakdown (observability.PhaseBreakdown): compile/warm-up
    wall-clock vs steady-state wall-clock vs host-sync + recompile counts
    from a record-only RecompileGuard. The breakdown lands in
    ``timings[name]`` (emitted as ``phase_timings`` in the BENCH json).

    ``warmup``/``timed`` are ITERATION counts. With ``tree_batch``>1 the
    loop drives fused batches (gbdt.train_batch) instead of per-tree
    updates — warm-up is at least 2 batches (first-dispatch compile + the
    committed-sharding steady variant) and the timed window is rounded to
    whole batches. Returns (steady_elapsed_s, guard, timed_iters_actual)."""
    from lightgbm_tpu.analysis.guards import RecompileGuard
    from lightgbm_tpu.observability import PhaseBreakdown
    g = bst._gbdt
    tb = max(1, tree_batch)
    if tb > 1:
        warm_steps = max(2, (warmup + tb - 1) // tb)
        timed_steps = max(1, timed // tb)
        iters = timed_steps * tb
        step = lambda: g.train_batch(tb)              # noqa: E731
    else:
        warm_steps, timed_steps, iters = warmup, timed, timed
        step = bst.update
    pb = PhaseBreakdown(name)
    with pb.compile_window():
        for _ in range(warm_steps):
            step()
        np.asarray(g.score).sum()             # drain queued warm-up work
    guard = RecompileGuard(label=name, fail=False)
    guard.register(g._step_fn if tb == 1 else g._batch_step_fns.get(tb),
                   "train_step")
    with guard:
        guard.mark_warm()
        with pb.steady_window(iters):
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                step()
            np.asarray(g.score).sum()           # the one intended host sync
            elapsed = time.perf_counter() - t0
    pb.attach_guard(guard.report())
    timings[name] = pb.to_dict()
    return elapsed, guard, iters


def _higgs_like(n_rows, n_features=28, seed=0):
    """Synthetic HIGGS-shaped binary problem with learnable nonlinear
    structure (products / squares like the derived kinematic features)."""
    rng = np.random.RandomState(seed)
    X = rng.rand(n_rows, n_features).astype(np.float32)
    logit = (X[:, 0] * 4 - X[:, 1] * 2 + X[:, 2] * X[:, 3] * 3
             + np.square(X[:, 4]) * 2 - X[:, 5] * X[:, 6] - 1.8)
    y = (logit + rng.randn(n_rows).astype(np.float32) * 0.75 > 0).astype(
        np.float32)
    return X, y


def _msltr_like(n_rows, n_features=137, seed=1, avg_query=120):
    """Synthetic MS-LTR-shaped ranking problem: lognormal query sizes
    (~avg_query docs), graded 0-4 labels from a noisy latent relevance."""
    rng = np.random.RandomState(seed)
    sizes = []
    total = 0
    while total < n_rows:
        q = max(8, int(rng.lognormal(np.log(avg_query), 0.6)))
        q = min(q, n_rows - total) if n_rows - total < 8 else q
        sizes.append(q)
        total += q
    sizes[-1] -= total - n_rows
    X = rng.rand(n_rows, n_features).astype(np.float32)
    latent = (X[:, 0] * 3 + X[:, 1] * X[:, 2] * 2 - X[:, 3]
              + np.square(X[:, 4]) * 1.5
              + rng.randn(n_rows).astype(np.float32) * 0.8)
    # grade into 0..4 by global quantiles (MSLR-ish label skew toward 0)
    qs = np.quantile(latent, [0.55, 0.75, 0.9, 0.97])
    y = np.searchsorted(qs, latent).astype(np.float32)
    return X, y, np.array(sizes, dtype=np.int32)


def _bosch_like(n_rows, n_features=968, group_size=8, p_active=0.75, seed=2):
    """Synthetic Bosch-shaped wide-sparse binary problem (the reference's
    GPU memory-table workload: Bosch is 1.184M x 968, ~81% sparse —
    docs/GPU-Performance.rst:183-186). Sparsity is STRUCTURED, not uniform:
    features come in mutually-exclusive blocks (station/sensor one-hot
    groups — the exact pattern EFB exists to exploit), so the EFB arm of
    the phase genuinely bundles ~group_size:1 while the no-EFB arm stores
    every raw column. Overall density = p_active / group_size (~9%)."""
    from scipy import sparse as sp
    rng = np.random.RandomState(seed)
    n_groups = n_features // group_size
    rows = np.arange(n_rows, dtype=np.int32)
    r_idx, c_idx, vals = [], [], []
    for g in range(n_groups):
        active = rng.rand(n_rows) < p_active
        member = rng.randint(0, group_size, n_rows)[active]
        r_idx.append(rows[active])
        c_idx.append((g * group_size + member).astype(np.int32))
        # low-cardinality values (sensor codes): real Bosch sparse columns
        # are near-binary; continuous values would give every feature ~B
        # bins and nothing could share a <=256-bin bundled column
        vals.append((rng.randint(1, 8, member.size) / 8.0).astype(np.float32))
    X = sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(r_idx), np.concatenate(c_idx))),
        shape=(n_rows, n_features))
    # label: latent from the first few groups' values (learnable signal)
    d0 = np.asarray(X[:, :3 * group_size].todense())
    latent = (d0[:, 0] * 3 + d0[:, group_size] * 2
              - d0[:, 2 * group_size] + d0[:, 1] * d0[:, group_size + 1] * 4)
    y = (latent + rng.randn(n_rows).astype(np.float32) * 0.4
         > np.median(latent)).astype(np.float32)
    return X, y


def run_sparse_phase():
    """Wide-sparse memory + throughput phase: quantifies the
    dense-u8 + EFB device-storage stance against the reference's sparse bin
    storage (src/io/sparse_bin.hpp:68) on a Bosch-shaped workload, next to
    the reference's own GPU memory table (docs/GPU-Performance.rst:183-186).

    THREE arms since the bundle-space split-finding redesign, each with its
    exact knob settings recorded next to its numbers:

    - ``bundlespace`` — enable_bundle=true, tpu_efb_unpack=false: the new
      native default (scan + routing + collectives all on bundled bins);
      the arm the r13 acceptance gate judges — it must at least match
      ``noefb`` throughput with a lower peak (the round-5 1.1-vs-3.8
      regression gone);
    - ``efb_unpack`` — enable_bundle=true, tpu_efb_unpack=true: the legacy
      unpack arm that MEASURED that regression, kept as the A/B;
    - ``noefb`` — enable_bundle=false: every raw column dense.

    Runs IN the calling process — the headline bench calls it directly,
    `bench.py --sparse` runs it alone: a chip belongs to one process, so a
    child started while the parent holds the TPU fails or hangs. jax's
    peak_bytes_in_use is cumulative per process, so an arm's peak is only
    reported when it exceeds the peak the process had reached before the
    arm (alone via --sparse every arm reports; after the 10.5M headline
    the smaller arms are masked and omitted). Arms run smallest-allocation
    first (bundlespace, then the unpack arm's [T,F,B,3] scan buffers, then
    the dense no-EFB matrix). Returns one dict (all keys
    ``sparse_*``-prefixed for the driver merge); ``LGBM_TPU_SPARSE_OUT``
    additionally banks the ledger-shaped payload for SPARSE_r<N>.json
    (comparability key ``|bundle=`` keeps the arms out of
    cross-representation judgement).
    """
    from lightgbm_tpu.utils.cache import resolve_compile_cache
    resolve_compile_cache()
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.memory import device_memory

    n_rows = int(os.environ.get("LGBM_TPU_BENCH_SPARSE_ROWS", "1000000"))
    n_feats = int(os.environ.get("LGBM_TPU_BENCH_SPARSE_FEATS", "968"))
    X, y = _bosch_like(n_rows, n_features=n_feats)
    out = {
        "sparse_rows": n_rows,
        "sparse_features": int(X.shape[1]),
        "sparse_density": round(float(X.nnz) / (X.shape[0] * X.shape[1]), 3),
    }
    base = dict(objective="binary", num_leaves=255, max_bin=255,
                learning_rate=0.1, min_data_in_leaf=100, verbose=-1,
                metric="none")
    arms = (("bundlespace", dict(enable_bundle=True, tpu_efb_unpack=False)),
            ("efb_unpack", dict(enable_bundle=True, tpu_efb_unpack=True)),
            ("noefb", dict(enable_bundle=False)))
    kernel = None
    peak_before = device_memory().get("peak_bytes") or 0
    for tag, knobs in arms:
        params = dict(base, **knobs)
        # honest arm naming: record each arm's exact settings next to its
        # numbers — "noefb" is an explicit enable_bundle=false run, not a
        # default, and the two EFB arms differ ONLY in the scan/routing
        # representation
        out[f"sparse_arm_{tag}"] = ",".join(
            f"{k}={str(v).lower()}" for k, v in sorted(knobs.items()))
        ds = lgb.Dataset(X, label=y, params=params)
        b = lgb.Booster(params=params, train_set=ds)
        if tag == "bundlespace":
            # the ledger headline is the bundlespace arm, so its resolved
            # kernel (auto resolves per KERNEL SHAPE CLASS — the bundled
            # arm's class differs from the dense arm's) is what the
            # |kernel= comparability key must carry
            kernel = b._gbdt.spec.hist_kernel
            out["sparse_efb_bundled"] = bool(b._gbdt.bundle is not None)
            out["sparse_device_cols_efb"] = int(b._gbdt.Xb.shape[1])
        elif tag == "noefb":
            out["sparse_device_cols_noefb"] = int(b._gbdt.Xb.shape[1])
        for _ in range(2):
            b.update()
        np.asarray(b._gbdt.score).sum()
        t0 = time.perf_counter()
        timed = 4
        for _ in range(timed):
            b.update()
        np.asarray(b._gbdt.score).sum()
        el = time.perf_counter() - t0
        out[f"sparse_mrow_tree_per_s_{tag}"] = _round_tp(
            n_rows * timed / el / 1e6)
        # observability/memory.py is the one home of the memory_stats()
        # read; a peak that did not move was set by an earlier phase
        peak = device_memory().get("peak_bytes")
        if peak and peak > peak_before:
            out[f"sparse_hbm_peak_gb_{tag}"] = round(peak / 2 ** 30, 2)
            peak_before = peak
        del b, ds
    # legacy alias: rounds <= 12 named the bundled arm's throughput
    # sparse_mrow_tree_per_s_efb; keep the series readable across rounds
    out["sparse_mrow_tree_per_s_efb"] = \
        out.get("sparse_mrow_tree_per_s_efb_unpack")
    # ledger-shaped payload: the bundlespace arm is the headline (the new
    # default); |bundle= in the comparability key keeps every arm from
    # being judged against a different representation's numbers
    ledger = {
        "metric": "sparse_train_throughput",
        "unit": "Mrow-tree/s",
        "platform": jax.default_backend(),
        "rows": n_rows,
        "kernel": kernel,
        "bundle": "bundlespace",
        "value": out.get("sparse_mrow_tree_per_s_bundlespace"),
        "hbm_peak_gb": out.get("sparse_hbm_peak_gb_bundlespace"),
        "noefb_mrow_tree_per_s": out.get("sparse_mrow_tree_per_s_noefb"),
        "efb_unpack_mrow_tree_per_s":
            out.get("sparse_mrow_tree_per_s_efb_unpack"),
        "noefb_hbm_peak_gb": out.get("sparse_hbm_peak_gb_noefb"),
        "arms": {t: out[f"sparse_arm_{t}"] for t, _ in arms},
        "sparse_features": out["sparse_features"],
        "sparse_density": out["sparse_density"],
        "efb_bundled": bool(out.get("sparse_efb_bundled")),
        # the r13 acceptance gate: bundling must actually ENGAGE on the
        # headline arm (a planner/win-ratio change silently training the
        # dense path would bank a dense number under bundle=bundlespace
        # and corrupt the comparability series) AND must no longer LOSE
        # to the dense arm on the workload EFB exists for
        "ok": bool(
            out.get("sparse_efb_bundled")
            and out.get("sparse_mrow_tree_per_s_bundlespace") is not None
            and out.get("sparse_mrow_tree_per_s_noefb") is not None
            and out["sparse_mrow_tree_per_s_bundlespace"]
            >= 0.95 * out["sparse_mrow_tree_per_s_noefb"]),
    }
    out["sparse_ledger"] = ledger
    sparse_out = os.environ.get("LGBM_TPU_SPARSE_OUT")
    if sparse_out:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(sparse_out, ledger, indent=1, sort_keys=True,
                          trailing_newline=True)
    return out


def _ndcg10(y, s, group):
    """Mean NDCG@10 with label_gain 2^l-1, discount 1/log2(2+i) —
    the reference's DCGCalculator defaults (dcg_calculator.cpp)."""
    gains = np.power(2.0, y) - 1.0
    disc = 1.0 / np.log2(np.arange(10) + 2.0)
    out, start = [], 0
    for g in group:
        seg_gain = gains[start:start + g]
        seg_score = s[start:start + g]
        k = min(10, g)
        top = np.argsort(-seg_score, kind="stable")[:k]
        dcg = float((seg_gain[top] * disc[:k]).sum())
        ideal = np.sort(seg_gain)[::-1][:k]
        idcg = float((ideal * disc[:k]).sum())
        if idcg > 0:
            out.append(dcg / idcg)
        start += g
    return float(np.mean(out)) if out else 0.0


def _auc(y, s):
    order = np.argsort(s)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y > 0.5
    npos, nneg = int(pos.sum()), int((~pos).sum())
    if npos == 0 or nneg == 0:
        return 0.5
    return float((ranks[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def run_bench(deadline, platform):
    # a stale snapshot from an in-process rerun must never masquerade as
    # this run's measurement
    _PARTIAL.clear()

    # persistent compile cache: a cold compile of the 10.5M-row train step
    # takes minutes; a warm cache keeps it out of the budget
    from lightgbm_tpu.utils.cache import resolve_compile_cache
    resolve_compile_cache()

    import lightgbm_tpu as lgb
    from lightgbm_tpu import observability as obs
    obs.maybe_configure_from_env()       # LGBM_TPU_TELEMETRY_DIR
    if os.environ.get("LGBM_TPU_BENCH_COSTS") == "1":
        # compile-time cost capture for every dispatch site this run
        # compiles (observability/costs.py; reports land in the telemetry
        # block below and in the perf ledger). Opt-in: cold, the duplicate
        # lower+compile of the 10.5M-row step costs minutes — with the warm
        # persistent cache above it is a disk hit.
        from lightgbm_tpu.observability import costs as obs_costs
        obs_costs.configure(enabled=True)

    kernel = os.environ.get("LGBM_TPU_BENCH_KERNEL", "auto")
    n_rows = int(os.environ.get("LGBM_TPU_BENCH_ROWS", str(10_500_000)))
    n_holdout = min(500_000, max(n_rows // 10, 10_000))
    # LGBM_TPU_BENCH_HEADLINE_ONLY=1: headline + AUC only
    headline_only = os.environ.get("LGBM_TPU_BENCH_HEADLINE_ONLY") == "1"

    # host-side data gen + binning cost ~55 s at full scale on a 1-core host
    # and is NOT part of the timed loop (the reference's benchmarks exclude
    # IO the same way, docs/Experiments.rst:99) — cache the raw matrix and
    # the binned dataset on disk. The key hashes the binning sources so a
    # binning-code change invalidates stale bins; writes are tmp+rename so
    # a deadline kill mid-write can never leave a truncated "valid" file.
    import hashlib
    import lightgbm_tpu as _pkg
    repo = os.path.dirname(os.path.abspath(__file__))
    cache_dir = os.path.join(repo, ".bench_cache")
    os.makedirs(cache_dir, exist_ok=True)
    src_hash = hashlib.md5()
    for rel in ("lightgbm_tpu/binning.py", "lightgbm_tpu/dataset.py"):
        with open(os.path.join(repo, rel), "rb") as fh:
            src_hash.update(fh.read())
    key = f"higgs_{n_rows}_h{n_holdout}_{src_hash.hexdigest()[:10]}"
    rawX_path = os.path.join(cache_dir, key + "_X.npy")
    rawy_path = os.path.join(cache_dir, key + "_y.npy")
    bin_path = os.path.join(cache_dir, key + "_b255.bin")

    def _atomic_save_npy(arr, path):
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:        # file handle: no .npy suffix games
            np.save(fh, arr)
        os.replace(tmp, path)

    if os.path.exists(rawX_path) and os.path.exists(rawy_path):
        X_all = np.load(rawX_path, mmap_mode="r")
        y_all = np.load(rawy_path, mmap_mode="r")
    else:
        X_all, y_all = _higgs_like(n_rows + n_holdout)
        _atomic_save_npy(X_all, rawX_path)
        _atomic_save_npy(y_all, rawy_path)
    Xt, yt = X_all[n_rows:], y_all[n_rows:]
    X, y = X_all[:n_rows], y_all[:n_rows]

    # fused multi-tree steps (tree_batch, boosting/gbdt.py): the headline
    # runs K iterations per jit dispatch — the dispatch-overhead fix this
    # bench exists to measure. LGBM_TPU_BENCH_TREE_BATCH=1 restores the
    # per-tree dispatch for A/B comparison.
    tree_batch = int(os.environ.get("LGBM_TPU_BENCH_TREE_BATCH", "4"))
    params = dict(
        objective="binary", num_leaves=255, max_bin=255, learning_rate=0.1,
        min_data_in_leaf=100, verbose=-1, metric="none",
        tpu_hist_kernel=kernel, tree_batch=tree_batch,
    )
    slots = int(os.environ.get("LGBM_TPU_BENCH_SLOTS", "0"))
    if slots:
        params["tpu_hist_slots"] = slots

    # attributable per-phase timing (observability.PhaseBreakdown): every
    # timed phase records compile_s / steady_s / host_syncs / recompiles
    # here; emitted as "phase_timings" in the JSON (docs/TPU-Performance.md)
    timings = {}

    # ---- quick-scale pre-bank ---------------------------------------------
    # Bank a 2.1M-row headline into _PARTIAL BEFORE the expensive full-scale
    # attempt, so a run that hits the global alarm inside the 10.5M compile
    # still reports a (clearly labeled, rows=2.1M) on-chip number.
    quick_rows = int(os.environ.get("LGBM_TPU_BENCH_QUICK_ROWS", "2100000"))
    if (n_rows > quick_rows
            and os.environ.get("LGBM_TPU_BENCH_QUICK", "1") != "0"):
        try:
            # private watchdog: a slow quick phase must leave the bulk of
            # the budget to the full-scale headline, not eat the global alarm
            with _phase_watchdog("quick",
                                 min(max(deadline() - 300, 60), 600)):
                qbin = os.path.join(
                    cache_dir,
                    f"higgs_{quick_rows}_{src_hash.hexdigest()[:10]}"
                    f"_b255.bin")
                if os.path.exists(qbin):
                    dq = lgb.Dataset(qbin)
                else:
                    # standalone gen, NOT a slice of the big matrix
                    Xq, yq = _higgs_like(quick_rows)
                    dq = lgb.Dataset(Xq, label=yq, params=params)
                    dq.construct()
                    dq.save_binary(qbin + ".tmp")
                    os.replace(qbin + ".tmp", qbin)
                bq = lgb.Booster(params=params, train_set=dq)
                # same fused dispatch path as the headline — the pre-banked
                # number must measure the same thing it stands in for
                elq, _, q_timed = _timed_update_phase(
                    "quick", bq, 2, 8, timings,
                    tree_batch=bq._gbdt.tree_batch)
                tq = quick_rows * q_timed / elq / 1e6
                _PARTIAL["result"] = {
                    "metric": "higgs_train_throughput",
                    "value": _round_tp(tq),
                    "unit": "Mrow-tree/s",
                    "vs_baseline": _round_ratio(
                        tq / BASELINE_MROW_TREE_PER_S),
                    "platform": platform,
                    "rows": quick_rows,
                    "kernel": bq._gbdt.spec.hist_kernel,
                    "residency": bq._gbdt.residency,
                    "phase_timings": timings,
                    "note": ("quick-scale pre-bank; the full-scale phase "
                             "did not complete"),
                }
                del bq, dq
        except BenchTimeout:
            raise                  # the watchdog alarm is one-shot: swallowing
                                   # it here would leave the full-scale phase
                                   # running unguarded
        except Exception:                                    # noqa: BLE001
            traceback.print_exc(file=sys.stderr)   # quick phase is insurance,
                                                   # never the point of failure

    if os.path.exists(bin_path):
        ds = lgb.Dataset(bin_path)
    else:
        # construct with the BENCH params so binning-relevant keys
        # (min_data_in_leaf -> filter_cnt, max_bin, sample_cnt) match what
        # Booster._setup_train would have used
        ds = lgb.Dataset(np.asarray(X), label=np.asarray(y), params=params)
        ds.construct()
        ds.save_binary(bin_path + ".tmp")
        os.replace(bin_path + ".tmp", bin_path)
    bst = lgb.Booster(params=params, train_set=ds)
    # what actually runs, read back from the booster's grower spec (not a
    # re-derivation of the auto-resolution rule, which would drift when the
    # pallas default flips back on) — the JSON must be unambiguous about this
    kernel_resolved = bst._gbdt.spec.hist_kernel

    timed = int(os.environ.get("LGBM_TPU_BENCH_TIMED_ITERS", "12"))
    warmup = 3 if timed >= 12 else 2
    # warm-up + timed loop under the per-phase breakdown and a record-only
    # recompile guard (fail=False: a recompile here is reported in the
    # JSON, not a crash — `bench.py --smoke` is the enforcing run). The
    # headline drives the FUSED multi-tree path (tree_batch, the dispatch-
    # overhead tentpole): K iterations per jit dispatch.
    elapsed, guard, timed = _timed_update_phase(
        "headline", bst, warmup, timed, timings,
        tree_batch=bst._gbdt.tree_batch)
    mrow_tree_per_s = n_rows * timed / elapsed / 1e6

    result = {
        "metric": "higgs_train_throughput",
        "value": _round_tp(mrow_tree_per_s),
        "unit": "Mrow-tree/s",
        "vs_baseline": _round_ratio(mrow_tree_per_s / BASELINE_MROW_TREE_PER_S),
        "platform": platform,
        "rows": n_rows,
        "kernel": kernel_resolved,
        "residency": bst._gbdt.residency,
        **({"hist_slots": slots} if slots else {}),
        "tree_batch": bst._gbdt.tree_batch,
        "recompiles_post_warmup": guard.report()["post_warmup_cache_misses"],
        "phase_timings": timings,
        "auc": None,
        "auc_parity_gap": None,
    }
    # device memory alongside throughput (the reference reports peak RES /
    # GPU memory: docs/Experiments.rst:158, docs/GPU-Performance.rst:183) —
    # via the shared backend-fallback helper (observability/memory.py)
    try:
        from lightgbm_tpu.observability.memory import device_memory
        peak = device_memory().get("peak_bytes")
        if peak:
            result["hbm_peak_gb"] = round(peak / 2 ** 30, 2)
    except Exception:                                        # noqa: BLE001
        pass

    # headline number exists from here on — if a later phase trips the
    # watchdog, main() still reports it
    _PARTIAL["result"] = dict(result)

    # ---- AUC on held-out rows: part of the HEADLINE phase -----------------
    # Computed here, BEFORE any optional phase, and re-banked into
    # _PARTIAL: a throughput claim without its quality check is not a
    # result — the AUC rides inside the headline snapshot, under its own
    # subdeadline.
    try:
        if deadline() > 60:
            with _phase_watchdog("headline_auc",
                                 min(max(deadline() - 45, 45), 480)):
                result["iters_for_auc"] = len(bst._gbdt.models)
                bst._finalize()
                result["auc"] = round(_auc(yt, bst.predict(Xt)), 6)
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["auc_error"] = str(e)[:200]
    _PARTIAL["result"] = dict(result)

    # Optional phases below must never void the headline result — a failure
    # or timeout there is recorded, not propagated; each runs behind its own
    # hard watchdog subdeadline (PhaseTimeout lands in the phase's *_error
    # field) so a hang degrades the JSON instead of voiding it.

    # ---- lambdarank companion: MS-LTR shape (docs/Experiments.rst:21,110) --
    # times the padded-query-bucket pairwise objective end-to-end and checks
    # ranking quality via NDCG@10 on held-out queries
    try:
        if deadline() > 300 and not headline_only:
            with _phase_watchdog("ranking", min(deadline() - 180, 900)):
                n_rank = int(os.environ.get(
                    "LGBM_TPU_BENCH_RANK_ROWS",
                    str(2_270_296 if platform != "cpu" else 120_000)))
                n_rank_hold = max(n_rank // 10, 10_000)
                Xr, yr, gr = _msltr_like(n_rank + n_rank_hold)
                cum = np.cumsum(gr)
                n_tr_q = int(np.searchsorted(cum, n_rank))
                n_tr = int(cum[n_tr_q - 1]) if n_tr_q else 0
                rank_params = dict(
                    objective="lambdarank", num_leaves=255, max_bin=255,
                    learning_rate=0.1, min_data_in_leaf=100, verbose=-1,
                    metric="none", tpu_hist_kernel=kernel)
                dsr = lgb.Dataset(Xr[:n_tr], label=yr[:n_tr],
                                  group=gr[:n_tr_q])
                br = lgb.Booster(params=rank_params, train_set=dsr)
                elr, _, rank_timed = _timed_update_phase("ranking", br, 2, 6,
                                                         timings)
                rank_tp = n_tr * rank_timed / elr / 1e6
                result["ranking_mrow_tree_per_s"] = _round_tp(rank_tp)
                result["ranking_vs_baseline"] = _round_ratio(
                    rank_tp / RANK_BASELINE_MROW_TREE_PER_S)
                result["ranking_rows"] = n_tr
                if deadline() > 60:
                    br._finalize()
                    result["ranking_ndcg10"] = round(
                        _ndcg10(yr[n_tr:], br.predict(Xr[n_tr:]),
                                gr[n_tr_q:]), 6)
                del br, dsr
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["ranking_error"] = str(e)[:200]

    # ---- real-data quality anchor: the reference's own binary example ----
    # (7k rows; trains its train.conf workload and puts our held-out AUC
    # next to what the reference C++ CLI produced on the same run — kills
    # the "synthetic AUC is self-referential" objection). Skipped in the
    # hermetic-CPU dry-run: B=255 histograms in emulated bf16 are ~27 s/iter
    # there.
    try:
        ref_dir = "/root/reference/examples/binary_classification"
        if deadline() > 240 and platform != "cpu" and os.path.isdir(ref_dir):
            with _phase_watchdog("reference_example",
                                 min(deadline() - 150, 420)):
                tr = np.loadtxt(os.path.join(ref_dir, "binary.train"))
                te = np.loadtxt(os.path.join(ref_dir, "binary.test"))
                ref_params = dict(
                    objective="binary", num_leaves=63, max_bin=255,
                    learning_rate=0.1, min_data_in_leaf=50,
                    min_sum_hessian_in_leaf=5.0, feature_fraction=0.8,
                    bagging_fraction=0.8, bagging_freq=5, verbose=-1,
                    metric="none", tpu_hist_kernel=kernel)
                bref = lgb.train(ref_params,
                                 lgb.Dataset(tr[:, 1:], label=tr[:, 0]),
                                 num_boost_round=100)
                result["reference_example_auc"] = round(
                    _auc(te[:, 0], bref.predict(te[:, 1:])), 6)
                # the reference CLI's valid auc on this exact run
                # (train.conf, 100 iters) — loaded from the provenance
                # fixture written by tests/gen_oracles.py (config/data
                # hashes recorded there)
                with open(os.path.join(
                        os.path.dirname(os.path.abspath(__file__)), "tests",
                        "fixtures", "oracles.json")) as fh:
                    result["reference_example_auc_oracle"] = \
                        json.load(fh)["bench_reference_example"]["auc"]
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["reference_example_error"] = str(e)[:200]

    # ---- GPU-config companion: max_bin=63 (docs/GPU-Performance.rst:105-125,
    # the reference's own GPU benchmark config; 4x narrower histograms) -----
    try:
        if deadline() > 240 and not headline_only:
            with _phase_watchdog("gpu_config", min(deadline() - 150, 900)):
                bin63 = os.path.join(cache_dir, key + "_b63.bin")
                if os.path.exists(bin63):
                    ds63 = lgb.Dataset(bin63)
                else:
                    ds63 = lgb.Dataset(np.asarray(X), label=np.asarray(y),
                                       params=dict(params, max_bin=63))
                    ds63.construct()
                    ds63.save_binary(bin63 + ".tmp")
                    os.replace(bin63 + ".tmp", bin63)
                b63 = lgb.Booster(params=dict(params, max_bin=63),
                                  train_set=ds63)
                # same dispatch mode as the headline: the 63-bin comparison
                # must isolate bin width, not re-add the per-tree dispatch
                # overhead
                el63, _, it63 = _timed_update_phase(
                    "gpu_config", b63, 2, 8, timings,
                    tree_batch=b63._gbdt.tree_batch)
                result["gpu_config_mrow_tree_per_s"] = _round_tp(
                    n_rows * it63 / el63 / 1e6)
                del b63, ds63
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["gpu_config_error"] = str(e)[:200]

    # ---- wide-sparse (Bosch-shaped) memory + throughput phase -------------
    # in-process: this process holds the chip (see run_sparse_phase)
    try:
        if (deadline() > 420
                and os.environ.get("LGBM_TPU_BENCH_SPARSE", "1") != "0"):
            # reserve ~210s so the wave-vs-exact parity gate (deadline > 150)
            # still runs after this phase
            with _phase_watchdog("sparse", min(deadline() - 200, 1560)):
                result.update(run_sparse_phase())
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["sparse_error"] = str(e)[:200]

    # ---- wave-vs-exact parity gate at reduced scale -----------------------
    # (tpu_wave_size=1 reproduces the reference's one-leaf-at-a-time order;
    #  the delta is the analog of the CPU-vs-GPU AUC table)
    try:
        if deadline() > 150 and not headline_only:
            with _phase_watchdog("parity", min(deadline() - 40, 420)):
                n_small = 400_000 if platform != "cpu" else 50_000
                n_small = min(n_small, n_rows)
                Xs, ys = X[:n_small], y[:n_small]
                small = dict(params, num_leaves=63, metric="none")
                b_wave = lgb.train(small, lgb.Dataset(Xs, label=ys),
                                   num_boost_round=15)
                b_exact = lgb.train(dict(small, tpu_wave_size=1),
                                    lgb.Dataset(Xs, label=ys),
                                    num_boost_round=15)
                auc_w = _auc(yt, b_wave.predict(Xt))
                auc_e = _auc(yt, b_exact.predict(Xt))
                gap = abs(auc_w - auc_e)
                result["auc_parity_gap"] = round(gap, 6)
                # reference GPU parity band: |CPU - GPU| AUC deltas are
                # ~3e-5..1e-3 (docs/GPU-Performance.rst:135-159); 2e-3 @ 15
                # iters
                result["auc_parity_ok"] = bool(gap < 2e-3)
    except BenchTimeout:
        raise
    except Exception as e:                                   # noqa: BLE001
        result["parity_error"] = str(e)[:200]

    # ---- telemetry summary block (docs/Observability.md) ------------------
    # counter snapshot + trace file path from the ONE process-wide registry
    # (PhaseBreakdown/RecompileGuard numbers land there too) — present only
    # when a telemetry dir is configured; phase_timings stays byte-
    # compatible with the BENCH_r* trajectory scripts either way.
    try:
        if obs.enabled():
            trace_file = obs.flush()
            snap = obs.snapshot()
            result["telemetry"] = {
                "counters": snap["counters"],
                "histograms": snap["histograms"],
                "trace_file": trace_file,
                "events_file": obs.jsonl_path(),
            }
            if snap.get("cost_reports"):
                # compiled-step cost reports ride in the BENCH json so the
                # perf ledger can flag cost-model drift across rounds
                result["telemetry"]["cost_reports"] = snap["cost_reports"]
            _PARTIAL["result"] = dict(result)
    except Exception as e:                                   # noqa: BLE001
        result["telemetry_error"] = str(e)[:200]

    return result


def main():
    """Headline bench on the chip. Exit 0 with ONE JSON line when the
    headline phase completed; non-zero with no result line when there is
    no TPU or the headline failed."""
    budget = int(os.environ.get("LGBM_TPU_BENCH_TIMEOUT", "900"))
    t_start = time.time()

    def deadline():
        return budget - (time.time() - t_start) - 30      # safety margin

    def on_alarm(signum, frame):
        raise BenchTimeout(f"bench exceeded {budget}s")

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"bench.py: no TPU (jax default backend is {platform!r}) — "
              f"the headline is a device measurement and has no CPU "
              f"stand-in", file=sys.stderr)
        return 1

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(budget)
    try:
        result = run_bench(deadline, platform)
    except BenchTimeout as e:
        # the alarm fired in a phase after the headline banked its
        # snapshot: report that snapshot, labeled; with no snapshot the
        # headline itself did not finish and the run failed
        result = _PARTIAL.get("result")
        if result is None:
            print(f"bench.py: {e} before the headline phase completed",
                  file=sys.stderr)
            return 1
        result.setdefault(
            "note", "later phases timed out; headline phase completed")
        result["phase_errors"] = str(e)[:300]
    finally:
        signal.alarm(0)
    print(json.dumps(result))
    return 0


def run_smoke():
    """`bench.py --smoke`: hermetic-CPU 5-iteration training run under the
    RecompileGuard (lightgbm_tpu/analysis/guards.py) — fails if the
    steady-state train step recompiles after warm-up: shape/static leaks
    into the step signature show up here as a nonzero miss count, before
    any TPU sees them. Also asserts a checkpoint save/resume round trip
    (docs/Fault-Tolerance.md) stays recompile-free: a mid-loop
    save_checkpoint and a full resume into a fresh booster must both keep
    hitting the warm executable. Additionally asserts the persistent XLA
    compile cache round-trips: a child training run populates a fresh
    cache dir, and an identical second run compiles nothing (writes no new
    cache entries) — the cache-hit path that keeps repeated step compiles
    out of every later run. Cost capture (observability/costs.py)
    is enabled for the WHOLE run: every guarded loop must stay
    recompile-free and host-sync-free with capture on, and the fused
    step's compile-time FLOPs/bytes are pinned to the goldens in
    tests/fixtures/cost_golden.json at the end. Prints one JSON line;
    exit 0 iff the guards hold."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu import observability as obs
    from lightgbm_tpu.observability import costs as obs_costs
    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard

    # telemetry is ON for the whole smoke run (the acceptance contract:
    # telemetry must not perturb any guarded loop below): honor an external
    # LGBM_TPU_TELEMETRY_DIR (`make trace` sets one), else use a temp dir
    # that is validated and removed at the end
    tel_dir = os.environ.get(obs.ENV_TELEMETRY_DIR)
    tel_tmp = None
    if not tel_dir:
        tel_tmp = tempfile.mkdtemp(prefix="lgbm_smoke_telemetry_")
        tel_dir = tel_tmp
    obs.configure(telemetry_dir=tel_dir)
    # cost capture is ON for the whole smoke run too: every guarded loop
    # below must stay recompile-free and host-sync-free WITH capture
    # enabled (capture happens at first dispatch, before mark_warm), and
    # the fused step's FLOPs/bytes are pinned to goldens at the end
    obs_costs.configure(enabled=True)

    n_rows = int(os.environ.get("LGBM_TPU_SMOKE_ROWS", "20000"))
    iters = int(os.environ.get("LGBM_TPU_SMOKE_ITERS", "5"))
    X, y = _higgs_like(n_rows)
    params = dict(objective="binary", num_leaves=31, max_bin=63,
                  learning_rate=0.1, min_data_in_leaf=20, verbose=-1,
                  metric="none", tpu_hist_kernel="xla")
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(2):            # warm-up: the compiles that are allowed
        bst.update()
    np.asarray(bst._gbdt.score).sum()

    guard = RecompileGuard(label="smoke")
    guard.register(bst._gbdt._step_fn, "train_step")
    ok, err = True, None
    try:
        with guard:
            guard.mark_warm()
            for _ in range(iters):
                bst.update()
            np.asarray(bst._gbdt.score).sum()   # drain queued work
    except GuardViolation as e:
        ok, err = False, str(e)
    report = guard.report()

    # ---- checkpoint save/resume round trip under the guard -----------------
    ck_dir = tempfile.mkdtemp(prefix="lgbm_smoke_ckpt_")
    resume_ok, resume_err, resume_misses = True, None, -1
    try:
        bst.save_checkpoint(ck_dir)
        ds2 = lgb.Dataset(X, label=y, params=params)
        bst2 = lgb.Booster(params=params, train_set=ds2)
        bst2.resume(ck_dir)
        for _ in range(2):            # same warm-up budget as a fresh run:
            bst2.update()             # first-step compile + the committed-
        np.asarray(bst2._gbdt.score).sum()   # sharding steady-state variant
        guard2 = RecompileGuard(label="smoke-resume")
        guard2.register(bst2._gbdt._step_fn, "train_step")
        try:
            with guard2:
                guard2.mark_warm()
                for i in range(iters):
                    bst2.update()
                    if i == iters // 2:
                        # an in-loop snapshot must not perturb the step
                        bst2.save_checkpoint(ck_dir)
                np.asarray(bst2._gbdt.score).sum()
        except GuardViolation as e:
            resume_ok, resume_err = False, str(e)
        resume_misses = guard2.report()["post_warmup_cache_misses"]
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        resume_ok, resume_err = False, f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(ck_dir, ignore_errors=True)

    # ---- persistent compile cache round trip -------------------------------
    # Two identical micro-training children against ONE fresh cache dir
    # placed from outside (JAX_COMPILATION_CACHE_DIR — the resolver in
    # utils/cache.py then sets nothing): the first must POPULATE it, the
    # second must be a pure cache hit (no new entries written = nothing
    # compiled). A CPU check of the round trip only; the directory is part
    # of the cache key, so both children must see the same path.
    cache_ok, cache_err = True, None
    cache_dir = tempfile.mkdtemp(prefix="lgbm_smoke_jaxcache_")
    child_code = (
        "from lightgbm_tpu.utils.hermetic import force_cpu_backend;"
        "force_cpu_backend();"
        "import numpy as np;"
        "import lightgbm_tpu as lgb;"
        "rng = np.random.RandomState(0);"
        "X = rng.rand(512, 8).astype(np.float32);"
        "y = (X[:, 0] > 0.5).astype(np.float32);"
        "p = dict(objective='binary', num_leaves=7, max_bin=15,"
        "         min_data_in_leaf=5, verbose=-1, metric='none');"
        "lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)"
    )
    try:
        cache_env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir,
                         JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        first_entries = None
        for attempt in range(2):
            r = subprocess.run([sys.executable, "-c", child_code],
                               env=cache_env, capture_output=True, text=True,
                               timeout=300)
            if r.returncode != 0:
                raise RuntimeError(f"cache child run {attempt} failed: "
                                   f"{(r.stderr or '')[-300:]}")
            entries = {e for e in os.listdir(cache_dir)
                       if e.endswith("-cache")}
            if attempt == 0:
                first_entries = entries
                if not entries:
                    raise RuntimeError(
                        "first run left the compile cache EMPTY — the "
                        "persistent cache is not working on this backend")
            elif entries - first_entries:
                raise RuntimeError(
                    f"second run wrote {len(entries - first_entries)} new "
                    f"cache entries — the cache-hit path recompiled")
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        cache_ok, cache_err = False, f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    # ---- telemetry overhead + Perfetto trace contract ----------------------
    # (docs/Observability.md) Two assertions:
    # 1. the FUSED step (tree_batch>1) with span recording ON compiles
    #    nothing after warm-up and pays zero additional host syncs vs the
    #    identical loop with recording OFF — telemetry is host bookkeeping
    #    at dispatch boundaries only;
    # 2. an engine.train run emits a Chrome trace that is valid trace-event
    #    JSON with the span nesting train -> iteration -> wave (what
    #    Perfetto renders).
    tel_ok, tel_err = True, None
    tel_misses, tel_syncs = -1, -1
    try:
        params_t = dict(params, tree_batch=2)
        ds_t = lgb.Dataset(X, label=y, params=params_t)
        bst_t = lgb.Booster(params=params_t, train_set=ds_t)
        g = bst_t._gbdt
        for _ in range(2):                     # warm-up: compiles allowed
            g.train_batch(2)
        np.asarray(g.score).sum()

        def _fused_loop(label):
            guard_f = RecompileGuard(label=label, fail=False)
            guard_f.register(g._batch_step_fns.get(2), "train_step")
            with guard_f:
                guard_f.mark_warm()
                for _ in range(iters):
                    g.train_batch(2)
                np.asarray(g.score).sum()      # the one intended host sync
            return guard_f.report()

        obs.configure(enabled=False)           # A: spans off
        base_rep = _fused_loop("smoke-telemetry-off")
        obs.configure(enabled=True)            # B: spans on, same executable
        tel_rep = _fused_loop("smoke-telemetry-on")
        tel_misses = tel_rep["post_warmup_cache_misses"]
        tel_syncs = tel_rep["host_syncs"]
        if tel_misses:
            raise RuntimeError(
                f"fused step recompiled with telemetry on: {tel_misses} "
                f"post-warm-up cache miss(es)")
        if tel_syncs > base_rep["host_syncs"]:
            raise RuntimeError(
                f"telemetry added host syncs inside the fused step: "
                f"{tel_syncs} vs baseline {base_rep['host_syncs']}")

        # engine-train run -> flushed trace with the full span hierarchy
        ds_e = lgb.Dataset(X, label=y, params=params_t)
        lgb.train(dict(params_t), ds_e, num_boost_round=6)
        trace_file = obs.trace_path()
        with open(trace_file) as fh:
            trace = json.load(fh)
        events = trace["traceEvents"]
        assert isinstance(events, list) and events, "empty traceEvents"

        def _contains(outer, inner):
            return (outer["tid"] == inner["tid"]
                    and outer["ts"] <= inner["ts"]
                    and inner["ts"] + inner.get("dur", 0)
                    <= outer["ts"] + outer["dur"] + 1)

        trains = [e for e in events if e.get("name") == "train"]
        batches = [e for e in events if e.get("name") == "tree_batch"]
        dispatches = [e for e in events if e.get("name") == "step.dispatch"]
        assert trains, "no train span in trace"
        assert batches, "no tree_batch spans in trace"
        assert dispatches, "no step.dispatch spans in trace"
        nested = [
            (t, b, d) for d in dispatches for b in batches for t in trains
            if _contains(b, d) and _contains(t, b)
            and d["parent_id"] == b["span_id"]]
        assert nested, \
            "spans are not nested train -> tree_batch -> step.dispatch"
        # the waves are COUNTED by the loop on the device, one record per
        # tree, published where the trees came to the host
        waves = obs.get_registry().summary("grow.waves")
        assert waves.count >= 6 and min(waves.values()) >= 1, \
            f"grow.waves: {waves.count} trees, {waves.values()}"
        # JSONL stream carries the counter snapshot next to the events
        jl = [json.loads(ln) for ln in open(obs.jsonl_path())
              if ln.strip()]
        assert any(r.get("type") == "counters"
                   and r.get("counters", {}).get("trees.trained")
                   for r in jl), "no counters record in the JSONL stream"
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        tel_ok, tel_err = False, f"{type(e).__name__}: {e}"
    finally:
        if tel_tmp:
            shutil.rmtree(tel_tmp, ignore_errors=True)

    # ---- robustness layer overhead (robustness/, docs/Fault-Tolerance.md) --
    # The self-healing path must be free when idle: with the hang watchdog
    # ARMED (heartbeat per dispatch, monitor thread polling) and a
    # checksummed checkpoint save in the loop, the fused step must add 0
    # post-warm-up recompiles and 0 new host syncs (enforced), and the
    # steady-state wall-clock overhead vs the bare loop is REPORTED
    # (target <2% on this shape; timing on a loaded CI box is advisory).
    rob_ok, rob_err = True, None
    rob_misses, rob_syncs, rob_overhead = -1, -1, None
    rob_ckpt_s = None
    try:
        import time as _time

        from lightgbm_tpu.robustness.watchdog import HangWatchdog
        params_r = dict(params, tree_batch=2)
        ds_r = lgb.Dataset(X, label=y, params=params_r)
        bst_r = lgb.Booster(params=params_r, train_set=ds_r)
        g_r = bst_r._gbdt
        for _ in range(2):                     # warm-up: compiles allowed
            g_r.train_batch(2)
        np.asarray(g_r.score).sum()
        rob_iters = max(iters, 10)
        ck_dir_r = tempfile.mkdtemp(prefix="lgbm_smoke_rob_ckpt_")

        def _guarded_loop(label, fail, beat_fn):
            """Identical guarded window both arms: rob_iters fused steps
            (+ optional watchdog beats) and one drain — sync counts compare
            like for like. The checksummed save lands AFTER the guard (its
            state fetch scales with the grown forest, so in-window it would
            skew the A/B; its recompile-freeness is already enforced by the
            smoke-resume section's in-loop save), timed separately."""
            guard_x = RecompileGuard(label=label, fail=fail)
            guard_x.register(g_r._batch_step_fns.get(2), "train_step")
            with guard_x:
                guard_x.mark_warm()
                t0 = _time.perf_counter()
                for _ in range(rob_iters):
                    g_r.train_batch(2)
                    if beat_fn:
                        beat_fn()
                np.asarray(g_r.score).sum()
                dt = _time.perf_counter() - t0
            t1 = _time.perf_counter()
            bst_r.save_checkpoint(ck_dir_r)
            ck_s = _time.perf_counter() - t1
            return guard_x.report(), dt, ck_s

        wd = HangWatchdog(timeout_s=3600.0, action="dump",
                          dump_dir=tel_dir)
        from lightgbm_tpu.robustness import distributed as _gdist
        from lightgbm_tpu.robustness.chaos import FakeKVStore
        try:
            base_rep_r, t_off, _ = _guarded_loop(
                "smoke-robustness-off", False, None)
            wd.start()
            # gang protocol armed for the ON arm (r17 acceptance: the
            # smoke stays 0-recompile/0-host-sync with heartbeat-lease
            # beats per dispatch AND the gang manifest commit on save) —
            # a FakeKVStore-backed 1-rank gang: every KV set/get is
            # host-only, so the guard proves the protocol adds no device
            # traffic
            _kv = FakeKVStore()
            _gdist.install_gang_override(_kv, rank=0, world=1)
            lease = _gdist.HeartbeatLease(
                client=_kv, rank=0, world=1,
                lease_timeout_s=30.0, interval_s=0.0)
            lease.beat(force=True)

            def _beat_all():
                wd.beat()
                lease.beat()
            rep_r, t_on, rob_ckpt_s = _guarded_loop(
                "smoke-robustness-on", True, _beat_all)
            if not _gdist.list_manifests(ck_dir_r):
                raise RuntimeError(
                    "gang override was live but save_checkpoint committed "
                    "no epoch manifest — the gang path did not engage")
            rob_ckpt_s = round(rob_ckpt_s, 4)
            rob_misses = rep_r["post_warmup_cache_misses"]
            rob_syncs = rep_r["host_syncs"]
            rob_overhead = round((t_on - t_off) / t_off, 4) if t_off > 0 \
                else None
            if rob_misses:
                raise RuntimeError(
                    f"fused step recompiled with the watchdog + heartbeat "
                    f"lease + gang checkpoint armed: {rob_misses} "
                    f"post-warm-up miss(es)")
            if rob_syncs > base_rep_r["host_syncs"]:
                raise RuntimeError(
                    f"the robustness layer added host syncs inside the "
                    f"fused loop: {rob_syncs} vs baseline "
                    f"{base_rep_r['host_syncs']}")
        finally:
            _gdist.uninstall_gang_override()
            wd.stop()
            shutil.rmtree(ck_dir_r, ignore_errors=True)
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        rob_ok, rob_err = False, f"{type(e).__name__}: {e}"

    # ---- EFB bundle-space guarded loop (docs/TPU-Performance.md "EFB") -----
    # A flags-shaped mini dataset where bundling ENGAGES (the smoke
    # headline is dense — no plan), trained under the guard on the native
    # bundle-space arm: the bundled scan, bundle-space routing table, and
    # code_feat tables must add ZERO post-warm-up recompiles and no host
    # syncs beyond the one intended drain — the r13 acceptance pin
    # "--smoke stays 0-recompile / 0-host-sync with bundling on".
    efb_ok, efb_err = True, None
    efb_misses, efb_syncs = -1, -1
    try:
        rng_e = np.random.RandomState(7)
        ge, pe = 6, 12
        flags_e = np.zeros((4096, ge * pe), np.float32)
        picks_e = rng_e.randint(0, pe, size=(4096, ge))
        for gi in range(ge):
            flags_e[np.arange(4096), gi * pe + picks_e[:, gi]] = 1.0
        y_e = (picks_e[:, 0] % 2).astype(np.float32)
        params_e = dict(params, num_leaves=15, max_bin=255)
        ds_e2 = lgb.Dataset(flags_e, label=y_e, params=params_e)
        bst_e = lgb.Booster(params=params_e, train_set=ds_e2)
        if bst_e._gbdt.bundle is None:
            raise RuntimeError("EFB did not engage on the flags dataset")
        if bst_e._gbdt.spec.efb_unpack:
            raise RuntimeError("expected the native bundle-space arm")
        for _ in range(2):
            bst_e.update()
        np.asarray(bst_e._gbdt.score).sum()
        guard_e = RecompileGuard(label="smoke-efb")
        guard_e.register(bst_e._gbdt._step_fn, "train_step")
        with guard_e:
            guard_e.mark_warm()
            for _ in range(iters):
                bst_e.update()
            np.asarray(bst_e._gbdt.score).sum()
        rep_e = guard_e.report()
        efb_misses = rep_e["post_warmup_cache_misses"]
        efb_syncs = rep_e["host_syncs"]
        if efb_misses:
            raise RuntimeError(
                f"bundled step recompiled: {efb_misses} post-warm-up "
                f"cache miss(es)")
        if efb_syncs > report["host_syncs"]:
            raise RuntimeError(
                f"bundling added host syncs: {efb_syncs} vs the dense "
                f"loop's {report['host_syncs']}")
    except GuardViolation as e:
        efb_ok, efb_err = False, str(e)
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        efb_ok, efb_err = False, f"{type(e).__name__}: {e}"

    # ---- linear-tree guarded loop (docs/Linear-Trees.md) -------------------
    # The linear_tree=true step — grow + path-feature walk + chunked moment
    # accumulation + batched Cholesky solve, all one jit — must add ZERO
    # post-warm-up recompiles and no host syncs beyond the dense loop's
    # one intended drain, and the standalone solve-leg cost site
    # (linear_cost_report) must land a capture so cost.* gauges and the
    # ledger drift gate cover the new leg.
    lin_ok, lin_err = True, None
    lin_misses, lin_syncs = -1, -1
    try:
        rng_l = np.random.RandomState(9)
        Xl = (rng_l.randn(4096, 8) * 2.0).astype(np.float64)
        yl = np.where(Xl[:, 0] > 0, 3.0 * Xl[:, 1], -2.0 * Xl[:, 2])
        Xl[rng_l.rand(4096, 8) < 0.02] = np.nan
        params_l = dict(params, objective="regression", num_leaves=15,
                        linear_tree=True, linear_lambda=0.01,
                        linear_max_features=4)
        ds_l = lgb.Dataset(Xl, label=yl, params=params_l)
        bst_l = lgb.Booster(params=params_l, train_set=ds_l)
        for _ in range(2):
            bst_l.update()
        np.asarray(bst_l._gbdt.score).sum()
        guard_l = RecompileGuard(label="smoke-linear")
        guard_l.register(bst_l._gbdt._step_fn, "train_step")
        with guard_l:
            guard_l.mark_warm()
            for _ in range(iters):
                bst_l.update()
            np.asarray(bst_l._gbdt.score).sum()
        rep_l = guard_l.report()
        lin_misses = rep_l["post_warmup_cache_misses"]
        lin_syncs = rep_l["host_syncs"]
        if lin_misses:
            raise RuntimeError(
                f"linear-tree step recompiled: {lin_misses} post-warm-up "
                f"cache miss(es) — the solve leg leaked a dynamic shape")
        if lin_syncs > report["host_syncs"]:
            raise RuntimeError(
                f"linear leaves added host syncs: {lin_syncs} vs the "
                f"dense loop's {report['host_syncs']}")
        from lightgbm_tpu.ops.linear import linear_cost_report
        lrep = linear_cost_report(
            n_rows=4096, num_features=bst_l._gbdt.spec.num_features,
            num_leaves=15, max_features=4,
            chunk_rows=bst_l._gbdt.spec.chunk_rows)
        if lrep.get("error"):
            raise RuntimeError(
                f"solve-leg cost capture failed: {lrep['error']}")
        if obs_costs.report(lrep["site"]) is None:
            raise RuntimeError("solve-leg cost report did not publish")
    except GuardViolation as e:
        lin_ok, lin_err = False, str(e)
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        lin_ok, lin_err = False, f"{type(e).__name__}: {e}"

    # ---- device-ingest guarded loop (ops/ingest.py) ------------------------
    # The same smoke dataset built from RAW rows under tpu_ingest=device
    # (explicit device skips the 65536-row auto threshold): the jitted bin
    # kernel must compile exactly ONCE across all chunks including the
    # zero-masked tail, the placed code matrix must equal the headline
    # (host-binned) booster's bit-for-bit, the training loop must stay
    # 0-recompile under the guard, and predictions must match the headline
    # run exactly — end-to-end training from raw arrays is bit-identical
    # to the host-binned path.
    ing_ok, ing_err = True, None
    ing_misses, ing_compiles = -1, None
    try:
        params_i = dict(params, tpu_ingest="device")
        ds_i = lgb.Dataset(X, label=y, params=params_i)
        bst_i = lgb.Booster(params=params_i, train_set=ds_i)
        g_i = bst_i._gbdt
        if g_i._ingest_report is None:
            raise RuntimeError("device ingest did not engage under "
                               "tpu_ingest=device")
        ing_compiles = g_i._ingest_report.get("compiles")
        if ing_compiles != 1:
            raise RuntimeError(f"ingest bin kernel compiled "
                               f"{ing_compiles}x, expected exactly 1")
        if not np.array_equal(np.asarray(bst._gbdt.Xb), np.asarray(g_i.Xb)):
            raise RuntimeError("device-ingested code matrix differs from "
                               "the host-binned placement")
        for _ in range(2):
            bst_i.update()
        np.asarray(g_i.score).sum()
        guard_i = RecompileGuard(label="smoke-ingest")
        guard_i.register(g_i._step_fn, "train_step")
        with guard_i:
            guard_i.mark_warm()
            for _ in range(iters):
                bst_i.update()
            np.asarray(g_i.score).sum()
        rep_i = guard_i.report()
        ing_misses = rep_i["post_warmup_cache_misses"]
        if ing_misses:
            raise RuntimeError(
                f"device-ingest booster recompiled: {ing_misses} "
                f"post-warm-up cache miss(es)")
        if not np.array_equal(bst.predict(X), bst_i.predict(X)):
            raise RuntimeError("device-ingest predictions differ from the "
                               "host-binned run")
    except GuardViolation as e:
        ing_ok, ing_err = False, str(e)
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        ing_ok, ing_err = False, f"{type(e).__name__}: {e}"

    # ---- trace-lint interference (analysis/trace_lint.py) ------------------
    # `make lint`'s trace tier traces and lowers the SHIPPED entry points
    # (contracts T001+, docs/Static-Analysis.md "Trace contracts"). Running
    # the whole registry in-process next to a live booster must add ZERO
    # post-warm-up recompiles to a subsequent guarded loop: make_jaxpr
    # never executes, and the contract programs trace on their own (tiny)
    # shapes, so the warm step executable stays warm. Cells whose builder
    # needs a multi-device topology (data8) are skipped on this
    # single-device smoke — `make lint` covers them under 8 virtual devices.
    trace_ok, trace_err = True, None
    trace_misses, trace_cells, trace_skipped = -1, 0, 0
    try:
        from lightgbm_tpu.analysis import contracts as treg
        import lightgbm_tpu.analysis.contracts.entries  # noqa: F401
        for cid in sorted(treg.CONTRACTS):
            c = treg.CONTRACTS[cid]
            for t in c.targets:
                try:
                    program = treg.build_program(c.entry, t.shape_class)
                except RuntimeError:      # topology-gated cell (needs >=2 dev)
                    trace_skipped += 1
                    continue
                bad = treg.evaluate(c, t, program)
                if bad:
                    raise RuntimeError(
                        f"trace contract {cid}@{t.shape_class}: {bad[0][1]}")
                trace_cells += 1
        guard_t = RecompileGuard(label="smoke-post-trace")
        guard_t.register(bst._gbdt._step_fn, "train_step")
        with guard_t:
            guard_t.mark_warm()
            for _ in range(iters):
                bst.update()
            np.asarray(bst._gbdt.score).sum()
        trace_misses = guard_t.report()["post_warmup_cache_misses"]
        if trace_misses:
            raise RuntimeError(
                f"trace tier perturbed the warm step: {trace_misses} "
                f"post-warm-up cache miss(es) in the follow-up loop")
    except GuardViolation as e:
        trace_ok, trace_err = False, str(e)
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        trace_ok, trace_err = False, f"{type(e).__name__}: {e}"

    # ---- golden cost pin for the fused step (observability/costs.py) -------
    # The fused train step's compile-time FLOPs/bytes-accessed must sit
    # inside the tolerance band of the committed goldens
    # (tests/fixtures/cost_golden.json) — a silent cost regression (an
    # accidental extra full-N pass, a dtype widening, a lost donation)
    # moves them 2x and fails CI here before any TPU sees it.
    cost_ok, cost_err = True, None
    cost_pin = {}
    try:
        rep = obs_costs.report("train_step.k2")
        if rep is None or rep.get("error"):
            raise RuntimeError(
                f"no cost report captured for the fused step: {rep}")
        cost_pin = {k: rep.get(k) for k in
                    ("flops", "bytes_accessed", "peak_hbm_bytes")}
        if n_rows == 20000:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)), "tests",
                    "fixtures", "cost_golden.json")) as fh:
                golden = json.load(fh)["smoke_train_step_k2"]
            bad = obs_costs.drift(rep, golden)
            if bad:
                raise RuntimeError(
                    f"fused-step cost drifted from golden: {bad}")
        else:
            cost_pin["golden_skipped"] = \
                f"non-default smoke shape (rows={n_rows})"
    except Exception as e:            # noqa: BLE001 — any failure fails CI
        cost_ok, cost_err = False, f"{type(e).__name__}: {e}"

    out = {"metric": "smoke_recompile_guard", "rows": n_rows, "iters": iters,
           "post_warmup_cache_misses": report["post_warmup_cache_misses"],
           "host_syncs": report["host_syncs"],
           "resume_post_warmup_cache_misses": resume_misses,
           "compile_cache_roundtrip_ok": cache_ok,
           "telemetry_ok": tel_ok,
           "telemetry_post_warmup_cache_misses": tel_misses,
           "telemetry_dir": None if tel_tmp else tel_dir,
           "cost_pin_ok": cost_ok,
           "cost_pin": cost_pin,
           "robustness_ok": rob_ok,
           "robustness_post_warmup_cache_misses": rob_misses,
           "robustness_host_syncs": rob_syncs,
           "robustness_overhead_frac": rob_overhead,
           "robustness_checkpoint_save_s": rob_ckpt_s,
           "efb_bundlespace_ok": efb_ok,
           "efb_post_warmup_cache_misses": efb_misses,
           "efb_host_syncs": efb_syncs,
           "linear_ok": lin_ok,
           "linear_post_warmup_cache_misses": lin_misses,
           "linear_host_syncs": lin_syncs,
           "ingest_ok": ing_ok,
           "ingest_post_warmup_cache_misses": ing_misses,
           "ingest_compiles": ing_compiles,
           "trace_lint_ok": trace_ok,
           "trace_lint_cells": trace_cells,
           "trace_lint_cells_skipped": trace_skipped,
           "trace_lint_post_warmup_cache_misses": trace_misses,
           "ok": (ok and resume_ok and cache_ok and tel_ok and cost_ok
                  and rob_ok and efb_ok and lin_ok and ing_ok
                  and trace_ok)}
    if err:
        out["error"] = err[:300]
    if resume_err:
        out["resume_error"] = resume_err[:300]
    if cache_err:
        out["compile_cache_error"] = cache_err[:300]
    if tel_err:
        out["telemetry_error"] = tel_err[:300]
    if cost_err:
        out["cost_pin_error"] = cost_err[:300]
    if rob_err:
        out["robustness_error"] = rob_err[:300]
    if efb_err:
        out["efb_error"] = efb_err[:300]
    if lin_err:
        out["linear_error"] = lin_err[:300]
    if ing_err:
        out["ingest_error"] = ing_err[:300]
    if trace_err:
        out["trace_lint_error"] = trace_err[:300]
    print(json.dumps(out))
    return 0 if out["ok"] else 1


# ------------------------------------------------------------ linear phase

def _piecewise_linear_data(n_rows, f=8, seed=17):
    """Piecewise-linear synthetic: the target's SLOPE switches with the
    sign of feature 0 — a constant-leaf tree must staircase what a linear
    leaf fits exactly, so accuracy-at-fixed-trees separates the two leaf
    models cleanly. A few NaN cells exercise the constant fallback."""
    rng = np.random.RandomState(seed)
    X = (rng.randn(n_rows, f) * 2.0).astype(np.float64)
    X[rng.rand(n_rows, f) < 0.01] = np.nan
    y = np.where(np.nan_to_num(X[:, 0]) > 0,
                 3.0 * np.nan_to_num(X[:, 1]) + 1.0,
                 -2.0 * np.nan_to_num(X[:, 2]) + 0.5) \
        + 0.05 * rng.randn(n_rows)
    return X, y


def run_linear(argv=None):
    """`bench.py --linear`: the piecewise-linear-leaves phase
    (linear_tree=true, ops/linear.py; docs/Linear-Trees.md). Hermetic CPU,
    like --smoke. A/B at FIXED tree count on a piecewise-linear synthetic:

    1. THROUGHPUT — linear vs constant leaves (the fit leg's measured
       price: path-feature walk + chunked moment accumulation + batched
       Cholesky, all fused into the train step);
    2. ACCURACY-AT-FIXED-TREES — holdout L2 of both arms after the SAME
       number of trees; the acceptance gate requires the linear arm to
       win (that is the workload's reason to exist);
    3. 0-RECOMPILE — the linear step (waves + solve leg) adds zero jit
       cache misses after warm-up (RecompileGuard);
    4. SERVING PARITY — a proto round trip through ServingEngine serves
       the linear model bit-identically to Booster.predict.

    Prints ONE JSON line (bench schema; linear="linear" keys it into its
    own perf-ledger comparability class); exit 0 iff the gates hold.
    LGBM_TPU_LINEAR_OUT banks the payload as LINEAR_r<N>.json."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import time

    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard
    from lightgbm_tpu.observability import costs as obs_costs

    n_rows = int(os.environ.get("LGBM_TPU_LINEAR_ROWS", "60000"))
    iters = int(os.environ.get("LGBM_TPU_LINEAR_ITERS", "8"))
    warmup = 2
    n_hold = max(n_rows // 5, 1000)
    X, y = _piecewise_linear_data(n_rows + n_hold)
    Xh, yh = X[n_rows:], y[n_rows:]
    X, y = X[:n_rows], y[:n_rows]
    # 16 leaves: coarse enough that a constant-leaf staircase visibly
    # underfits the piecewise-linear ramps the linear leaves fit exactly —
    # the A/B separates on MODEL CLASS, not tree count
    base = dict(objective="regression", num_leaves=16, max_bin=63,
                learning_rate=0.2, min_data_in_leaf=20, verbose=-1,
                metric="none", tpu_hist_kernel="xla", seed=11)
    lam, kmax = 0.01, 4

    out = {"metric": "linear_train_throughput", "unit": "Mrow-tree/s",
           "platform": "cpu", "rows": n_rows, "iters": iters,
           "n_devices": 1, "linear": "linear",
           "linear_lambda": lam, "linear_max_features": kmax}
    ok, err = True, []

    def timed_arm(params, guard=None):
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(warmup):
            bst.update()
        np.asarray(bst._gbdt.score).sum()
        if guard is not None:
            guard.register(bst._gbdt._step_fn, "train_step")
        t0 = time.perf_counter()
        if guard is not None:
            with guard:
                guard.mark_warm()
                for _ in range(iters):
                    bst.update()
                np.asarray(bst._gbdt.score).sum()
        else:
            for _ in range(iters):
                bst.update()
            np.asarray(bst._gbdt.score).sum()
        el = time.perf_counter() - t0
        return bst, n_rows * iters / el / 1e6

    # ---- constant arm (the baseline both gates judge against) --------------
    b_const, tp_const = timed_arm(dict(base, linear_tree=False))
    out["constant_mrow_tree_per_s"] = _round_tp(tp_const)
    mse_const = float(np.mean((b_const.predict(Xh) - yh) ** 2))
    out["mse_constant"] = round(mse_const, 6)

    # ---- linear arm under the guard ----------------------------------------
    guard = RecompileGuard(label="linear")
    params_l = dict(base, linear_tree=True, linear_lambda=lam,
                    linear_max_features=kmax)
    try:
        b_lin, tp_lin = timed_arm(params_l, guard=guard)
    except GuardViolation as e:
        ok = False
        err.append(str(e)[:300])
        b_lin, tp_lin = None, None
    rep = guard.report()
    out["recompiles_post_warmup"] = rep["post_warmup_cache_misses"]
    out["kernel"] = "xla"
    out["value"] = _round_tp(tp_lin) if tp_lin else None
    out["linear_vs_constant"] = _round_ratio(tp_lin / tp_const) \
        if tp_lin else None
    if b_lin is not None:
        out["kernel"] = b_lin._gbdt.spec.hist_kernel
        mse_lin = float(np.mean((b_lin.predict(Xh) - yh) ** 2))
        out["mse_linear"] = round(mse_lin, 6)
        out["accuracy_gain_frac"] = round(1.0 - mse_lin / mse_const, 4)
        n_lin = sum(1 for t in b_lin.trees
                    for fset in (t.leaf_features or []) if len(fset))
        n_leaves = sum(t.num_leaves for t in b_lin.trees)
        out["linear_leaves"] = n_lin
        out["total_leaves"] = n_leaves
        if n_lin == 0:
            ok = False
            err.append("every leaf degraded to constant — the linear arm "
                       "trained no linear models")
        # the acceptance gate: linear leaves must BEAT constant leaves at
        # fixed tree count on the piecewise-linear shape
        if mse_lin >= mse_const:
            ok = False
            err.append(f"accuracy gate failed: linear mse {mse_lin:.5f} "
                       f">= constant {mse_const:.5f} at {warmup + iters} "
                       f"trees")
        # ---- serving parity: proto round trip, bit-identical ---------------
        import tempfile
        with tempfile.TemporaryDirectory(prefix="lgbm_linear_") as td:
            pb = os.path.join(td, "m.proto")
            b_lin.save_model(pb)
            from lightgbm_tpu.serving import ServingEngine
            with ServingEngine(pb, params=dict(verbose=-1)) as eng:
                probe = Xh[:256]
                same = bool(np.array_equal(b_lin.predict(probe),
                                           eng.predict(probe)))
            out["identical_to_serving"] = same
            if not same:
                ok = False
                err.append("ServingEngine predictions differ from "
                           "Booster.predict on the linear model")
        # solve-leg cost site (observability/costs.py linear_cost_report):
        # the standalone fit leg's compile-time FLOPs/bytes, for the
        # cost.* gauges and the ledger drift gate
        from lightgbm_tpu.ops.linear import linear_cost_report
        lrep = linear_cost_report(
            n_rows=n_rows, num_features=b_lin._gbdt.spec.num_features,
            num_leaves=b_lin._gbdt.spec.num_leaves, max_features=kmax,
            chunk_rows=b_lin._gbdt.spec.chunk_rows)
        if not lrep.get("error"):
            out["cost_reports"] = {lrep["site"]: {
                k: lrep.get(k) for k in
                ("flops", "bytes_accessed", "peak_hbm_bytes")
                if lrep.get(k) is not None}}

    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_LINEAR_OUT", "")
    if out_path:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# ------------------------------------------------------------ stream phase

def run_stream(argv=None):
    """`bench.py --stream`: the out-of-core streaming phase
    (tpu_residency=stream, ops/stream.py; docs/TPU-Performance.md
    "Out-of-core streaming"). Hermetic CPU, like --smoke. What it proves:

    1. AUTO FALLBACK — an artificial per-device HBM budget is configured
       at 1/4 of the raw binned-code bytes, so the dataset is >= 4x the
       budget and ``tpu_residency=auto`` must resolve to stream (asserted).
    2. IDENTITY — the streamed run's predictions are BIT-identical to the
       device-resident run on the same data (tpu_row_compact=false arm).
    3. 0-RECOMPILE — the streamed steady-state wave loop adds zero jit
       cache misses after warm-up (RecompileGuard over every streamed
       entrypoint).
    4. MEASURED OVERLAP — throughput streamed vs resident, the prefetch
       stall fraction (stall seconds / streamed steady seconds), and a
       forced no-prefetch arm (LGBM_TPU_STREAM_NO_PREFETCH) so the double
       buffer's win is a measured delta, not an assumption.

    Prints ONE JSON line (bench schema + stream extras; residency=stream
    keys it into its own perf-ledger comparability class); exit 0 iff the
    identity + guard assertions hold. LGBM_TPU_STREAM_OUT writes the same
    payload to a file for banking as STREAM_r<N>.json."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import time

    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard

    n_rows = int(os.environ.get("LGBM_TPU_STREAM_ROWS", "60000"))
    iters = int(os.environ.get("LGBM_TPU_STREAM_ITERS", "8"))
    warmup = 2
    X, y = _higgs_like(n_rows)
    # budget = raw binned-code bytes / 4: the dataset alone is >= 4x it
    budget = max(1, (n_rows * X.shape[1]) // 4)
    base = dict(objective="binary", num_leaves=31, max_bin=63,
                learning_rate=0.1, min_data_in_leaf=20, verbose=-1,
                metric="none", tpu_hist_kernel="xla", tpu_hist_chunk=8192,
                tpu_row_compact=False, seed=11)

    def build(params):
        ds = lgb.Dataset(X, label=y, params=params)
        return lgb.Booster(params=params, train_set=ds)

    def timed_loop(bst):
        for _ in range(warmup):
            bst.update()
        np.asarray(bst._gbdt.score).sum()
        t0 = time.perf_counter()
        for _ in range(iters):
            bst.update()
        np.asarray(bst._gbdt.score).sum()
        return time.perf_counter() - t0

    out = {"metric": "stream_train_throughput", "unit": "Mrow-tree/s",
           "platform": "cpu", "rows": n_rows, "iters": iters,
           "kernel": "xla", "residency": "stream", "n_devices": 1,
           "hbm_budget_bytes": budget}
    ok, err = True, []

    # ---- resident arm (the identity + throughput baseline) -----------------
    b_dev = build(dict(base, tpu_residency="device"))
    t_dev = timed_loop(b_dev)
    tp_dev = n_rows * iters / t_dev / 1e6
    out["resident_mrow_tree_per_s"] = _round_tp(tp_dev)

    # ---- streamed arm: auto fallback + guard + stall accounting ------------
    b_st = build(dict(base, tpu_residency="auto",
                      tpu_hbm_budget_bytes=budget))
    g = b_st._gbdt
    if g.residency != "stream":
        ok = False
        err.append(f"auto residency resolved to {g.residency!r}, expected "
                   f"stream (budget={budget})")
    else:
        store = g._stream_store
        raw_bytes = store.n_rows_padded * store.num_cols
        out["dataset_bytes"] = raw_bytes
        out["stream"] = store.describe()
        if raw_bytes < 4 * budget:
            ok = False
            err.append(f"dataset {raw_bytes} B is not >= 4x the "
                       f"{budget} B budget")
        pf = g._stream
        guard = RecompileGuard(label="stream")
        for _ in range(warmup):
            b_st.update()
        np.asarray(g.score).sum()
        for name, fn in g._streamed_grower.jit_entrypoints():
            guard.register(fn, name)
        for name in ("pre", "prep", "shrink", "apply"):
            guard.register(g._stream_fns[name], name)
        stalls0, stall_s0 = pf.stalls, pf.stall_seconds
        bytes0 = pf.bytes_h2d
        try:
            with guard:
                guard.mark_warm()
                t0 = time.perf_counter()
                for _ in range(iters):
                    b_st.update()
                np.asarray(g.score).sum()
                t_st = time.perf_counter() - t0
        except GuardViolation as e:
            ok = False
            err.append(str(e)[:300])
            t_st = float("nan")
        rep = guard.report()
        out["recompiles_post_warmup"] = rep["post_warmup_cache_misses"]
        # a guard violation leaves t_st = nan — keep the one-JSON-line
        # contract (bare NaN is not valid JSON) by nulling derived metrics
        finite = t_st > 0          # False for nan
        tp_st = n_rows * iters / t_st / 1e6 if finite else None
        out["value"] = _round_tp(tp_st) if finite else None
        out["stream_vs_resident"] = _round_ratio(tp_st / tp_dev) \
            if finite else None
        out["stream_bytes_h2d"] = pf.bytes_h2d - bytes0
        out["prefetch_stalls"] = pf.stalls - stalls0
        out["prefetch_stall_fraction"] = round(
            (pf.stall_seconds - stall_s0) / t_st, 4) if finite else None
        # identity: streamed === resident, bit for bit
        ps, pd = b_st.predict(X), b_dev.predict(X)
        out["identical_to_resident"] = bool(np.array_equal(ps, pd))
        if not out["identical_to_resident"]:
            ok = False
            err.append(f"streamed predictions differ from resident "
                       f"(max abs diff {float(np.max(np.abs(ps - pd)))})")

        # ---- forced no-prefetch arm: the overlap, measured -----------------
        os.environ["LGBM_TPU_STREAM_NO_PREFETCH"] = "1"
        try:
            b_np = build(dict(base, tpu_residency="stream",
                              tpu_stream_shard_rows=(
                                  store.local_shard_rows)))
            for _ in range(warmup):
                b_np.update()
            np.asarray(b_np._gbdt.score).sum()
            pf_np = b_np._gbdt._stream
            # stall baseline AFTER warm-up: the fraction must cover the
            # timed window only (the streamed arm subtracts the same way)
            np_stall0 = pf_np.stall_seconds
            t0 = time.perf_counter()
            for _ in range(iters):
                b_np.update()
            np.asarray(b_np._gbdt.score).sum()
            t_np = time.perf_counter() - t0
            out["no_prefetch_mrow_tree_per_s"] = _round_tp(
                n_rows * iters / t_np / 1e6)
            out["overlap_speedup_vs_no_prefetch"] = \
                _round_ratio(t_np / t_st) if finite else None
            out["no_prefetch_stall_fraction"] = round(
                (pf_np.stall_seconds - np_stall0) / t_np, 4)
            del b_np
        finally:
            os.environ.pop("LGBM_TPU_STREAM_NO_PREFETCH", None)

    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_STREAM_OUT", "")
    if out_path:
        # the one atomic JSON writer (observability/export.py, pid-suffixed
        # tmp — concurrent runs never clobber each other's in-flight file)
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# ------------------------------------------------------------ ingest phase

def run_ingest(argv=None):
    """`bench.py --ingest`: the device-side dataset ingest phase
    (tpu_ingest=device, ops/ingest.py; docs/TPU-Performance.md
    "Device-side ingest"). Hermetic CPU, like --smoke. What it proves:

    1. BIT IDENTITY — the device-binned code matrix (real region, the
       row/column padding zeros, AND the packed byte layout) equals the
       host oracle (dataset.bin_dense_host + np.pad + pack_codes_host)
       exactly. Identity is a hard gate, not a tolerance band.
    2. THROUGHPUT — steady-state device ingest (H2D feed + jitted bin +
       pack, stall-accounted) runs >= 3x the host oracle's rows/s; the
       one-off compile pass is reported separately as device_cold_s.
    3. 0-RECOMPILE — every chunk, including the zero-masked tail, reuses
       the first chunk's executable (traced row offset; RecompileGuard
       over the jitted bin kernel).
    4. MEASURED OVERLAP — the prefetch stall fraction plus a forced
       no-prefetch arm (LGBM_TPU_INGEST_NO_PREFETCH) so the double
       buffer's win is a measured delta, not an assumption.

    Prints ONE JSON line (bench schema + ingest extras; ingest=device
    keys it into its own perf-ledger comparability class). Exit 0 iff
    identity + guard + floor hold. LGBM_TPU_INGEST_OUT writes the same
    payload to a file for banking as INGEST_r<N>.json."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import time

    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import bin_dense_host, construct_dataset
    from lightgbm_tpu.ops import ingest as ingest_mod
    from lightgbm_tpu.ops.histogram import code_mode_for
    from lightgbm_tpu.ops.stream import pack_codes_host

    n_rows = int(os.environ.get("LGBM_TPU_INGEST_ROWS", "200000"))
    X, y = _higgs_like(n_rows)
    rng = np.random.RandomState(7)
    X[rng.rand(n_rows) < 0.05, 3] = np.nan      # exercise the NaN-bin path
    cfg = Config.from_params({"max_bin": 255, "verbose": -1,
                              "tpu_ingest": "host"})
    cd = construct_dataset(X, y, cfg)
    mappers = cd.mappers
    real_idx = np.asarray(cd.real_feature_idx)
    dtype = cd.code_dtype
    F = len(real_idx)
    # residency-style padding: an extra row block beyond the 256-multiple
    # (a whole zero-masked tail chunk region) and +4 feature columns — the
    # identity check covers the padding zeros, not just the real region
    n_pad = ((n_rows + 255) // 256) * 256 + 256
    cols_pad = F + 4

    out = {"metric": "ingest_throughput", "unit": "Mrow/s",
           "platform": "cpu", "rows": n_rows, "num_cols": cols_pad,
           "kernel": "xla", "n_devices": 1, "ingest": "device",
           "max_bin": 255}
    ok, err = True, []

    # ---- host oracle arm (the single-pass bin_dense_host) ------------------
    bin_dense_host(X, mappers, real_idx, dtype, n_rows)     # warm caches
    t0 = time.perf_counter()
    Xb_host = bin_dense_host(X, mappers, real_idx, dtype, n_rows)
    t_host = time.perf_counter() - t0
    host_mrow = n_rows / t_host / 1e6
    out["host_mrow_per_s"] = _round_tp(host_mrow)
    ref = np.zeros((n_pad, cols_pad), dtype)
    ref[:n_rows, :F] = Xb_host

    # ---- device arm: cold (compile) pass, then steady under the guard ------
    ing = ingest_mod.DeviceIngestor(mappers, num_cols=cols_pad,
                                    n_rows=n_rows, out_dtype=dtype)
    kw = dict(n_rows=n_rows, n_rows_padded=n_pad, num_cols=cols_pad,
              out_dtype=dtype, ingestor=ing)
    t0 = time.perf_counter()
    codes, _rep_cold = ingest_mod.device_ingest(X, mappers, real_idx, **kw)
    out["device_cold_s"] = round(time.perf_counter() - t0, 4)
    guard = RecompileGuard(label="ingest")
    guard.register(ing._fn, "ingest_bin")
    try:
        with guard:
            guard.mark_warm()
            t0 = time.perf_counter()
            codes, rep = ingest_mod.device_ingest(X, mappers, real_idx, **kw)
            t_dev = time.perf_counter() - t0
    except GuardViolation as e:
        ok = False
        err.append(str(e)[:300])
        t_dev, rep = float("nan"), _rep_cold
    out["recompiles_post_warmup"] = guard.report()["post_warmup_cache_misses"]
    finite = t_dev > 0                # False for nan
    dev_mrow = n_rows / t_dev / 1e6 if finite else None
    out["value"] = _round_tp(dev_mrow) if finite else None
    out["device_vs_host"] = _round_ratio(dev_mrow / host_mrow) \
        if finite else None
    out["compiles"] = ing.compiles
    out["chunks"] = rep["n_chunks"]
    out["chunk_rows"] = rep["chunk_rows"]
    out["bytes_h2d"] = rep["bytes_h2d"]
    out["prefetch_stalls"] = rep["stalls"]
    out["prefetch_stall_fraction"] = round(rep["stall_fraction"], 4) \
        if finite else None

    # ---- bit identity: real region + padding zeros + packed layout ---------
    ident = bool(np.array_equal(np.asarray(codes), ref))
    mode = code_mode_for(int(Xb_host.max()), dtype)
    out["packed_mode"] = mode
    ing_p = ingest_mod.DeviceIngestor(mappers, num_cols=cols_pad,
                                      n_rows=n_rows, out_dtype=dtype,
                                      code_mode=mode)
    packed_dev, _ = ingest_mod.device_ingest(
        X, mappers, real_idx, n_rows=n_rows, n_rows_padded=n_pad,
        num_cols=cols_pad, out_dtype=dtype, code_mode=mode, ingestor=ing_p)
    ident_packed = bool(np.array_equal(np.asarray(packed_dev),
                                       pack_codes_host(ref, mode)))
    out["identical_to_host"] = ident and ident_packed
    if not ident:
        ok = False
        err.append("device codes differ from the host oracle")
    if not ident_packed:
        ok = False
        err.append(f"device {mode}-packed bytes differ from pack_codes_host")

    # ---- forced no-prefetch arm: the overlap, measured ---------------------
    os.environ["LGBM_TPU_INGEST_NO_PREFETCH"] = "1"
    try:
        t0 = time.perf_counter()
        _codes_np, rep_np = ingest_mod.device_ingest(X, mappers, real_idx,
                                                     **kw)
        t_np = time.perf_counter() - t0
        out["no_prefetch_mrow_per_s"] = _round_tp(n_rows / t_np / 1e6)
        out["overlap_speedup_vs_no_prefetch"] = _round_ratio(t_np / t_dev) \
            if finite else None
        out["no_prefetch_stall_fraction"] = round(rep_np["stall_fraction"], 4)
    finally:
        os.environ.pop("LGBM_TPU_INGEST_NO_PREFETCH", None)

    # ---- gates -------------------------------------------------------------
    if out["recompiles_post_warmup"]:
        ok = False
        err.append(f"{out['recompiles_post_warmup']} post-warm-up ingest "
                   f"recompile(s) — the traced row offset leaked a static")
    if finite and out["device_vs_host"] is not None \
            and out["device_vs_host"] < 3.0:
        ok = False
        err.append(f"device ingest only {out['device_vs_host']}x the host "
                   f"oracle — below the 3x acceptance floor")

    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_INGEST_OUT", "")
    if out_path:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# ------------------------------------------------------------- serve phase

def run_serve(argv=None):
    """`bench.py --serve`: the production-inference phase
    (lightgbm_tpu/serving, docs/Serving.md). Hermetic CPU, like --smoke.
    What it proves:

    1. INTERCHANGE — the model travels train -> protobuf file ->
       ServingEngine, and the served predictions are BIT-identical to the
       training booster's in-memory predict() on the same rows (asserted;
       the traversal is integer rank-exact on device and the leaf sum is
       host f64 in tree order).
    2. 0-RECOMPILE — after warmup() AOT-compiles the bucket ladder, closed
       and open-loop load across every batch-size shape adds ZERO jit
       cache misses (RecompileGuard over the engine's entrypoints; the
       padding ladder is the whole point).
    3. LATENCY/THROUGHPUT — closed-loop p50/p99 latency and rows/s at
       several concurrency x batch-size shapes, plus an open-loop Poisson
       arm through the MicroBatcher (queue delay included — the SLO view),
       with batch fill fraction and queue peak from the metrics registry.

    Prints ONE JSON line (bench schema + serve extras; the `serve` field
    keys it into its own perf-ledger comparability class and `p99_ms`
    joins the regression gate); exit 0 iff identity + guard assertions
    hold. LGBM_TPU_SERVE_OUT banks the payload as SERVE_r<N>.json."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu import observability as obs
    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard
    from lightgbm_tpu.serving import MicroBatcher, ServingEngine
    from lightgbm_tpu.serving.loadgen import run_closed_loop, run_open_loop

    n_rows = int(os.environ.get("LGBM_TPU_SERVE_ROWS", "20000"))
    n_trees = int(os.environ.get("LGBM_TPU_SERVE_TREES", "30"))
    X, y = _higgs_like(n_rows)
    bst = lgb.train({"objective": "binary", "num_leaves": 31, "max_bin": 63,
                     "learning_rate": 0.1, "min_data_in_leaf": 20,
                     "verbose": -1, "metric": "none", "seed": 7},
                    lgb.Dataset(X, label=y), num_boost_round=n_trees)
    probe = X[:2048]
    p_train = bst.predict(probe)

    buckets = os.environ.get("LGBM_TPU_SERVE_BUCKETS", "1,8,64,512")
    out = {"metric": "serve_bench", "unit": "rows/s", "platform": "cpu",
           "rows": n_rows, "kernel": "xla", "n_devices": 1,
           "trees": n_trees, "buckets": [int(b) for b in buckets.split(",")]}
    ok, err = True, []

    with tempfile.TemporaryDirectory() as td:
        proto_path = os.path.join(td, "model.proto")
        bst.save_model(proto_path)
        engine = ServingEngine(
            proto_path, params={"serve_buckets": buckets,
                                "serve_max_batch_rows": 512,
                                "serve_max_wait_ms": 2.0, "verbose": -1})
        out["warmup_compiles"] = int(
            obs.get_registry().counter("serve.bucket_compiles").value)

        # ---- interchange identity: proto -> engine === in-memory train ----
        p_served = engine.predict(probe)
        out["identical_to_train_predict"] = bool(
            np.array_equal(p_train, p_served))
        if not out["identical_to_train_predict"]:
            ok = False
            err.append("served predictions differ from the training "
                       "booster's predict() (max abs diff %g)"
                       % float(np.max(np.abs(p_train - p_served))))

        # ---- load under the recompile pin --------------------------------
        guard = RecompileGuard(label="serve")
        for name, fn in engine.jit_entrypoints():
            guard.register(fn, name)
        closed, open_arm = {}, None
        try:
            with guard:
                guard.mark_warm()
                for batch, conc in ((1, 1), (8, 4), (64, 4), (512, 2)):
                    r = run_closed_loop(
                        engine.predict, X, batch, conc,
                        requests_per_worker=max(240 // (conc * max(
                            batch // 8, 1)), 10))
                    closed[f"b{batch}xc{conc}"] = r
                    if r["errors"]:
                        ok = False
                        err.append(f"closed-loop errors at b{batch}xc{conc}: "
                                   f"{r['errors'][:2]}")
                with MicroBatcher(engine) as mb:
                    open_arm = run_open_loop(
                        mb.predict, X, batch_rows=4, rate_rps=200.0,
                        duration_s=2.0, seed=11)
                    if open_arm["errors"]:
                        ok = False
                        err.append(f"open-loop errors: "
                                   f"{open_arm['errors'][:2]}")
        except GuardViolation as e:
            ok = False
            err.append(str(e)[:300])
        rep = guard.report()
        out["recompiles_post_warmup"] = rep["post_warmup_cache_misses"]
        if rep["post_warmup_cache_misses"]:
            ok = False
            err.append(f"serving recompiled after warmup: "
                       f"{rep['misses_by_entrypoint']}")

        snap = obs.snapshot()
        fill = (snap.get("histograms") or {}).get("serve.batch_fill_frac")
        lat = (snap.get("summaries") or {}).get("serve.latency_ms")
        out["closed"] = closed
        out["open"] = open_arm
        out["batch_fill_frac_mean"] = fill.get("mean") if fill else None
        out["queue_peak"] = (snap.get("gauges") or {}).get("serve.queue_peak")
        out["snapshot_latency"] = {k: lat.get(k) for k in
                                   ("p50", "p99", "count")} if lat else None

    # headline: the biggest closed-loop shape's throughput + its p99 —
    # `serve` names the shape so the ledger only compares like with like
    head_key = "b512xc2"
    head = closed.get(head_key) or {}
    out["serve"] = f"closed|{head_key}"
    out["value"] = head.get("rows_per_s")
    out["p99_ms"] = head.get("p99_ms")
    out["p50_ms"] = head.get("p50_ms")
    if not isinstance(out["value"], (int, float)) or not out["value"]:
        ok = False
        err.append(f"no headline throughput measured for {head_key}")

    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_SERVE_OUT", "")
    if out_path:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# ------------------------------------------------------- serve-chaos phase

def run_serve_chaos(argv=None):
    """`bench.py --serve-chaos`: the serving-resilience phase
    (docs/Serving.md "Resilience", serving/resilience.py). Hermetic CPU,
    deterministic fault injection through the engine's DispatchChaos hook
    — injected faults travel the exact production dispatch path. Arms:

    1. OVERLOAD BURST — an open-loop Poisson arrival stream offered ABOVE
       capacity (every dispatch artificially slowed) against a bounded
       micro-batcher queue: excess requests SHED with the typed
       ServerOverloadedError (never queued, never OOM, never a hang),
       every served response is verified bit-identical to the training
       booster, and the shed rate + p99-under-overload are the banked
       headline the perf ledger gates (`|serve_chaos=` key).
    2. DISPATCH FAILURES — an injected failure burst trips the circuit
       breaker: requests DURING the burst still answer bit-identically
       (host-predictor fallback), health() reads `degraded`, and the
       background probe re-warms the device path back to `ready`.
    3. SLOW-DISPATCH HANG — a wedged dispatch under per-request
       deadlines: every waiting caller unblocks with DeadlineExceededError
       at ~its deadline (never the hang duration), queued requests behind
       the hang are dropped at dequeue WITHOUT spending a dispatch, and
       serving recovers bit-identically once the hang clears.
    4. MID-LOAD RELOAD — a hot reload() swaps models under open-loop
       traffic: zero request errors, every response matches exactly ONE
       of the two model versions; a deliberately corrupted candidate
       (injected verify failure) ROLLS BACK leaving the live version
       serving.
    5. STEADY-STATE PIN — after all chaos, a RecompileGuard over the
       engine's entrypoints proves resilience adds ZERO steady-state
       recompiles.

    Prints ONE JSON line (bench schema; `serve_chaos` names the
    fault-injection shape for the ledger, `shed_rate` and `p99_ms` feed
    the regression gate); exit 0 iff every arm holds.
    LGBM_TPU_SERVE_CHAOS_OUT banks the payload as SERVE_CHAOS_r<N>.json."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import threading

    import lightgbm_tpu as lgb
    from lightgbm_tpu import observability as obs
    from lightgbm_tpu.analysis.guards import GuardViolation, RecompileGuard
    from lightgbm_tpu.serving import (DeadlineExceededError, DispatchChaos,
                                      MicroBatcher, ReloadError,
                                      ServingEngine)
    from lightgbm_tpu.serving.loadgen import run_open_loop

    n_rows = int(os.environ.get("LGBM_TPU_SERVE_CHAOS_ROWS", "8000"))
    n_trees = int(os.environ.get("LGBM_TPU_SERVE_CHAOS_TREES", "20"))
    X, y = _higgs_like(n_rows)
    common = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": -1,
              "metric": "none"}
    bst = lgb.train(dict(common, seed=7), lgb.Dataset(X, label=y),
                    num_boost_round=n_trees)
    bst2 = lgb.train(dict(common, seed=11, num_leaves=15),
                     lgb.Dataset(X, label=y),
                     num_boost_round=max(n_trees // 2, 4))

    out = {"metric": "serve_chaos", "unit": "rows/s", "platform": "cpu",
           "rows": n_rows, "kernel": "xla", "n_devices": 1,
           "trees": n_trees, "serve_chaos": "open|b4|overload"}
    ok, err = True, []

    engine = ServingEngine(
        bst, params={"serve_buckets": "1,8,64", "serve_max_batch_rows": 64,
                     "serve_max_wait_ms": 1.0, "serve_breaker_failures": 3,
                     "serve_breaker_window_s": 30.0,
                     "serve_probe_interval_s": 0.05, "verbose": -1})
    chaos = DispatchChaos()
    engine.chaos = chaos
    probe = X[:256]
    want = bst.predict(probe)

    # ---- arm 1: overload burst sheds, never hangs, served bits exact ----
    # capacity is capped (every dispatch slowed) so the offered Poisson
    # load genuinely exceeds it; overload clients carry deadlines (the
    # real serving shape — without one a caller camps on the saturated
    # replica instead of letting admission control shed it)
    chaos.slowdown_s = 0.05
    mismatches = [0]

    def predict_checked(Xr):
        served = mb.predict(Xr)
        if not np.array_equal(served, bst.predict(Xr)):
            mismatches[0] += 1
            raise AssertionError("served bits differ under overload")
        return served

    t_arm = time.monotonic()
    with MicroBatcher(engine, max_batch_rows=64, max_wait_ms=1.0,
                      max_queue_rows=64, deadline_ms=500.0) as mb:
        r = run_open_loop(predict_checked, X[:512], batch_rows=4,
                          rate_rps=float(os.environ.get(
                              "LGBM_TPU_SERVE_CHAOS_RPS", "600")),
                          duration_s=2.0, seed=13, stop_on_error=False)
    chaos.slowdown_s = 0.0
    arm_wall = time.monotonic() - t_arm
    sheds = sum("ServerOverloadedError" in e for e in r["errors"])
    deadlines = sum("DeadlineExceededError" in e for e in r["errors"])
    other = [e for e in r["errors"]
             if "ServerOverloadedError" not in e
             and "DeadlineExceededError" not in e]
    offered = r["requests"] + len(r["errors"])
    shed_rate = round(sheds / offered, 4) if offered else None
    out["overload"] = {
        "offered_rps": r["offered_rps"], "requests_offered": offered,
        "served": r["requests"], "shed": sheds,
        "deadline_exceeded": deadlines, "shed_rate": shed_rate,
        "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
        "rows_per_s": r["rows_per_s"], "wall_s": round(arm_wall, 2),
        "other_errors": other[:3]}
    out["shed_rate"] = shed_rate
    out["value"] = r["rows_per_s"]
    out["p99_ms"] = r["p99_ms"]
    out["p50_ms"] = r["p50_ms"]
    if sheds == 0:
        ok = False
        err.append("overload arm: offered load above capacity but nothing "
                   "was shed — admission control did not engage")
    if r["requests"] == 0:
        ok = False
        err.append("overload arm: nothing was served — shedding must "
                   "protect capacity, not replace it")
    if other or mismatches[0]:
        ok = False
        err.append(f"overload arm: unexpected non-typed errors {other[:2]} "
                   f"(+{mismatches[0]} bit mismatches)")
    if arm_wall > 60.0:
        ok = False
        err.append(f"overload arm took {arm_wall:.0f}s — a bounded queue "
                   f"with deadlines must not stall the drivers")

    # ---- arm 2: dispatch failures -> degraded -> probe recovery ---------
    # 3 failures trip the breaker; the surplus keeps the PROBE failing too
    # (injected faults travel every dispatch), holding the engine
    # observably degraded while the latency arm runs — recovery follows
    # once the injected burst exhausts
    chaos.arm_failures(23)
    degraded_ok = True
    for _ in range(3):
        degraded_ok &= bool(np.array_equal(engine.predict(probe), want))
    health_mid = engine.health()
    t0 = obs.clock()
    lat_deg = []
    for _ in range(20):
        t1 = obs.clock()
        degraded_ok &= bool(np.array_equal(engine.predict(probe), want))
        lat_deg.append((obs.clock() - t1) * 1e3)
    from lightgbm_tpu.serving.loadgen import latency_stats
    deg_stats = latency_stats(lat_deg)
    t_rec = obs.clock()
    while engine.health() != "ready" and obs.clock() - t_rec < 15.0:
        time.sleep(0.05)
    recovered = engine.health() == "ready"
    post_ok = bool(np.array_equal(engine.predict(probe), want))
    out["degraded"] = {
        "health_during_burst": health_mid, "bit_identical": degraded_ok,
        "p99_ms": deg_stats["p99_ms"], "recovered_ready": recovered,
        "recovery_s": round(obs.clock() - t0, 3),
        "bit_identical_after_recovery": post_ok}
    if not (health_mid == "degraded" and degraded_ok and recovered
            and post_ok):
        ok = False
        err.append(f"degrade arm failed: {out['degraded']}")

    # ---- arm 3: slow-dispatch hang under deadlines ----------------------
    chaos.arm_hang(1.5, n=1)
    outcomes = []
    with MicroBatcher(engine, max_batch_rows=8, max_wait_ms=1.0,
                      deadline_ms=200.0) as mb:
        d0 = chaos.dispatches

        def call():
            t1 = obs.clock()
            try:
                mb.predict(X[:2])
                outcomes.append(("ok", obs.clock() - t1))
            except DeadlineExceededError:
                outcomes.append(("deadline", obs.clock() - t1))
            except Exception as e:                            # noqa: BLE001
                outcomes.append((repr(e), obs.clock() - t1))

        threads = [threading.Thread(target=call, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
            time.sleep(0.08)
        for t in threads:
            t.join(timeout=20)
        hang_dispatches = chaos.dispatches - d0
        time.sleep(1.3)            # let the hung dispatch clear
        post = mb.predict(X[:5])
    hang_ok = (len(outcomes) == 3
               and all(k == "deadline" for k, _ in outcomes)
               and all(dt < 1.2 for _, dt in outcomes)
               and hang_dispatches == 1
               and np.array_equal(post, bst.predict(X[:5])))
    out["hang"] = {"outcomes": [(k, round(dt, 3)) for k, dt in outcomes],
                   "dispatches_spent": hang_dispatches,
                   "recovered_bit_identical": hang_ok}
    if not hang_ok:
        ok = False
        err.append(f"hang arm failed: {out['hang']}")

    # ---- arm 4: mid-load reload (atomic) + corrupted-candidate rollback -
    pool = X[:40]
    exp1 = {n: bst.predict(pool[:n]) for n in (2, 3, 5)}
    exp2 = {n: bst2.predict(pool[:n]) for n in (2, 3, 5)}
    stop = threading.Event()
    versions_seen = set()
    reload_errors = []
    with MicroBatcher(engine, max_batch_rows=16, max_wait_ms=1.0) as mb:
        def worker(w):
            i = 0
            while not stop.is_set():
                n = (2, 3, 5)[(w + i) % 3]
                i += 1
                try:
                    served = mb.predict(pool[:n])
                except Exception as e:                        # noqa: BLE001
                    reload_errors.append(repr(e))
                    return
                if np.array_equal(served, exp1[n]):
                    versions_seen.add(1)
                elif np.array_equal(served, exp2[n]):
                    versions_seen.add(2)
                else:
                    reload_errors.append(f"mixed-version response (n={n})")
                    return

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        new_version = engine.reload(bst2, verify_rows=128)
        time.sleep(0.3)
        # corrupted candidate: inject dispatch failures through the verify
        # path -> warmup/verification fails -> rollback, still serving v2
        chaos.arm_failures(1000)
        rollback_raised = False
        try:
            engine.reload(bst, verify_rows=64)
        except ReloadError:
            rollback_raised = True
        chaos.arm_failures(0)
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=20)
    snap = obs.snapshot()
    reload_ok = (not reload_errors and versions_seen == {1, 2}
                 and new_version == 2 and rollback_raised
                 and engine.describe()["model_version"] == 2
                 and np.array_equal(engine.predict(pool[:5]), exp2[5])
                 and snap["counters"].get("serve.reloads") == 1
                 and snap["counters"].get("serve.reload_rollbacks") == 1)
    out["reload"] = {
        "errors": reload_errors[:3], "versions_seen": sorted(versions_seen),
        "rollback_raised": rollback_raised,
        "model_version": engine.describe()["model_version"],
        "reloads": snap["counters"].get("serve.reloads"),
        "rollbacks": snap["counters"].get("serve.reload_rollbacks")}
    if not reload_ok:
        ok = False
        err.append(f"reload arm failed: {out['reload']}")

    # ---- arm 5: steady-state stays 0-recompile with resilience on -------
    guard = RecompileGuard(label="serve-chaos")
    for name, fn in engine.jit_entrypoints():
        guard.register(fn, name)
    try:
        with guard:
            guard.mark_warm()
            for n in (1, 3, 8, 9, 64, 33):
                engine.predict(X[:n])
            with MicroBatcher(engine, max_batch_rows=64,
                              max_wait_ms=1.0) as mb:
                for n in (2, 4, 7):
                    mb.predict(X[:n])
    except GuardViolation as e:
        ok = False
        err.append(str(e)[:300])
    rep = guard.report()
    out["recompiles_post_warmup"] = rep["post_warmup_cache_misses"]
    if rep["post_warmup_cache_misses"]:
        ok = False
        err.append(f"steady-state recompiled with resilience enabled: "
                   f"{rep['misses_by_entrypoint']}")
    engine.close()
    out["health_final"] = "down"       # engine closed above, by contract

    snap = obs.snapshot()
    out["counters"] = {k: v for k, v in snap["counters"].items()
                       if k in ("serve.shed", "serve.deadline_exceeded",
                                "serve.breaker_trips",
                                "serve.breaker_recoveries",
                                "serve.host_fallback", "serve.reloads",
                                "serve.reload_rollbacks")}
    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:600]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_SERVE_CHAOS_OUT", "")
    if out_path:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# ------------------------------------------------------------- chaos phase

def run_chaos(argv=None):
    """`bench.py --chaos`: the self-healing recovery phase
    (docs/Fault-Tolerance.md). Hermetic CPU. What it measures:

    1. KILL -9 RECOVERY — a supervised CLI train child is SIGKILLed once
       two checkpoints are banked; the supervisor relaunches with
       resume_from=auto. Reported: measured recovery time (MTTR — failure
       to the relaunched child's next checkpoint), restart count, total
       disruption (supervised wall-clock minus the clean run's), and the
       bit-identity of the final model vs a fault-free run (asserted).
    2. CORRUPT-LATEST RECOVERY — the newest snapshot is bit-flipped
       between runs; resume_from=auto's lineage walk falls back one
       interval and the continued model is bit-identical (asserted).
    3. STEADY-STATE OVERHEAD — in-process A/B of the robustness layer
       (hang watchdog armed + interval checkpoints with CRC envelopes) vs
       the bare loop, reported as a fraction (the <2% target lives in
       docs/Fault-Tolerance.md; `--smoke` enforces the 0-recompile /
       0-host-sync half of the contract).

    Prints ONE JSON line; exit 0 iff both recovery arms are bit-identical.
    LGBM_TPU_CHAOS_OUT banks the payload to a file."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import shutil
    import tempfile
    import time

    import lightgbm_tpu as lgb
    from lightgbm_tpu.cli import main as cli_main
    from lightgbm_tpu.robustness.checkpoint import (CheckpointManager,
                                                    verify_checkpoint)
    from lightgbm_tpu.robustness.supervisor import Supervisor

    n_rows = int(os.environ.get("LGBM_TPU_CHAOS_ROWS", "10000"))
    iters = int(os.environ.get("LGBM_TPU_CHAOS_ITERS", "20"))
    seed = int(os.environ.get("LGBM_TPU_CHAOS_SEED", "1234"))
    work = tempfile.mkdtemp(prefix="lgbm_bench_chaos_")
    out = {"metric": "chaos_recovery", "platform": "cpu", "rows": n_rows,
           "iters": iters, "seed": seed}
    ok, err = True, []
    try:
        X, y = _higgs_like(n_rows)
        data = os.path.join(work, "train.csv")
        with open(data, "w") as fh:
            for i in range(n_rows):
                fh.write(",".join([f"{y[i]:.6g}"]
                                  + [f"{v:.6g}" for v in X[i]]) + "\n")

        def args_for(model, ck_dir=None, rounds=iters):
            a = [f"data={data}", "task=train", "objective=binary",
                 "num_leaves=31", "max_bin=63", "learning_rate=0.1",
                 "min_data_in_leaf=20", "metric=none", "seed=17",
                 f"num_trees={rounds}", "verbose=-1",
                 f"output_model={model}"]
            if ck_dir:
                a += [f"checkpoint_dir={ck_dir}", "checkpoint_interval=2"]
            return a

        child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                         PYTHONPATH=os.path.dirname(os.path.abspath(__file__)))

        def spawn(extra_hook=None):
            children = []

            def _sp(argv):
                proc = subprocess.Popen(
                    [sys.executable, "-m", "lightgbm_tpu"] + list(argv),
                    env=child_env, cwd=work)
                children.append(proc)
                if extra_hook:
                    extra_hook(proc, len(children))
                return proc
            return _sp

        # ---- clean supervised baseline -------------------------------------
        clean_model = os.path.join(work, "clean.txt")
        t0 = time.perf_counter()
        sup0 = Supervisor(args_for(clean_model,
                                   os.path.join(work, "ck_clean")),
                          seed=seed, spawn_fn=spawn())
        if sup0.run() != 0:
            raise RuntimeError("clean supervised run failed")
        t_clean = time.perf_counter() - t0
        out["clean_s"] = round(t_clean, 2)

        # ---- kill -9 arm ---------------------------------------------------
        from lightgbm_tpu.robustness.chaos import kill_after_checkpoints
        kill_model = os.path.join(work, "kill9.txt")
        ck_kill = os.path.join(work, "ck_kill")

        def kill_hook(proc, child_no):
            if child_no == 1:
                kill_after_checkpoints(proc, ck_kill, n=2)

        t0 = time.perf_counter()
        sup = Supervisor(args_for(kill_model, ck_kill), seed=seed,
                         backoff_base_s=0.1, backoff_max_s=1.0,
                         spawn_fn=spawn(kill_hook))
        rc = sup.run()
        t_kill = time.perf_counter() - t0
        identical = (rc == 0 and open(kill_model).read()
                     == open(clean_model).read())
        out["kill9"] = {
            "exit_codes": sup.exit_codes,
            "restarts": sup.restarts,
            "recovery_s": ([round(s, 2) for s in sup.recovery_seconds]
                           or None),
            "total_s": round(t_kill, 2),
            "disruption_s": round(t_kill - t_clean, 2),
            "identical_to_clean": identical,
        }
        if not (identical and sup.restarts >= 1):
            ok = False
            err.append(f"kill9 arm: identical={identical} "
                       f"restarts={sup.restarts} rc={rc}")

        # ---- corrupt-latest arm --------------------------------------------
        ck_cor = os.path.join(work, "ck_cor")
        half_model = os.path.join(work, "half.txt")
        cor_model = os.path.join(work, "corrupt.txt")
        cli_main(args_for(half_model, ck_cor, rounds=iters // 2))
        latest = CheckpointManager(ck_cor).latest()
        raw = bytearray(open(latest, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(latest, "wb").write(bytes(raw))
        assert not verify_checkpoint(latest)[0]
        cli_main(args_for(cor_model, ck_cor) + ["resume_from=auto"])
        identical = open(cor_model).read() == open(clean_model).read()
        out["corrupt_latest"] = {"identical_to_clean": identical,
                                 "corrupted": os.path.basename(latest)}
        if not identical:
            ok = False
            err.append("corrupt-latest arm: resumed model differs")

        # ---- steady-state overhead (in-process) ----------------------------
        # Booster.update() bypasses engine.train, where the watchdog and
        # the interval-checkpoint callback actually live — arm both
        # EXPLICITLY here (one HangWatchdog with its monitor thread, a
        # heartbeat per dispatch, a checksummed save every 5 iterations)
        # so the A/B measures the real robustness layer, not two bare
        # loops. The jitted step is shared across arms (same booster
        # params/dataset shapes), so neither arm pays a fresh compile.
        from lightgbm_tpu.robustness.watchdog import HangWatchdog
        params = dict(objective="binary", num_leaves=31, max_bin=63,
                      learning_rate=0.1, min_data_in_leaf=20, verbose=-1,
                      metric="none", seed=17)
        ck_ovh = os.path.join(work, "ck_ovh")

        def timed(robust):
            ds = lgb.Dataset(X, label=y, params=params)
            bst = lgb.Booster(params=params, train_set=ds)
            for _ in range(2):
                bst.update()
            np.asarray(bst._gbdt.score).sum()
            wd = None
            if robust:
                wd = HangWatchdog(timeout_s=3600.0, action="dump",
                                  dump_dir=work).start()
            try:
                t0 = time.perf_counter()
                for i in range(iters):
                    bst.update()
                    if wd is not None:
                        wd.beat(i)
                    if robust and (i + 1) % 5 == 0:
                        bst.save_checkpoint(ck_ovh)
                np.asarray(bst._gbdt.score).sum()
                return time.perf_counter() - t0
            finally:
                if wd is not None:
                    wd.stop()

        t_bare = timed(False)
        t_rob = timed(True)
        if not CheckpointManager(ck_ovh).list_checkpoints():
            raise RuntimeError("overhead arm wrote no checkpoints — the "
                               "robustness side of the A/B did not run")
        out["overhead_frac"] = round((t_rob - t_bare) / t_bare, 4)
        out["overhead_includes"] = ("hang watchdog armed + heartbeat/iter "
                                    "+ interval-5 CRC checkpoints")
    except Exception as e:                # noqa: BLE001 — fail the phase
        ok = False
        err.append(f"{type(e).__name__}: {e}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_CHAOS_OUT", "")
    if out_path:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


def run_chaos_dist(argv=None):
    """`bench.py --chaos-dist`: the DISTRIBUTED fault-tolerance matrix
    (docs/Fault-Tolerance.md "Distributed fault tolerance"). Hermetic CPU;
    gangs are real multi-process jax.distributed clusters or multi-threaded
    FakeKVStore simulations — deterministic either way. The arms:

    1. LEASE EXPIRY — a peer rank beats its heartbeat lease once and dies;
       the survivor's pre-wave probe must raise PeerLostError NAMING rank 1
       within the lease deadline. Detection latency p50/p99 over repeated
       trials is banked (the detection half of fleet MTTR).
    2. KV FLAP DURING INIT — jax.distributed.initialize loses the first
       coordination-service handshake; init_distributed must re-run the
       partial-init reset (shutdown/clear) and join on attempt 2, never
       die on attempt 1.
    3. MANIFEST/SHARD MISMATCH — a 2-rank gang commits two epochs, then
       rank 1's newest shard rots; BOTH ranks' resolve_resume falls back a
       FULL epoch together (shed_epochs banked; a mixed-iteration resume is
       never attempted) and `checkpoint --verify` on the bad epoch exits 2.
    4. KILL -9 ONE RANK MID-EPOCH (skipped under LGBM_TPU_CHAOS_DIST_FAST)
       — a real 2-process gang trains over jax.distributed; rank 1
       SIGKILLs itself after two manifest commits. The survivor must exit
       145 (comm loss, not a hang), FleetSupervisor relaunches the gang
       with resume_from=auto, and the final model is bit-identical to a
       fault-free gang run. Fleet MTTR (failure -> first new epoch after
       relaunch) is banked.
    5. ELASTIC 8->4 SHRINK (skipped under FAST) — a checkpoint written at 8
       simulated devices is resumed at 4: WITHOUT tpu_reshard_on_resume the
       run must refuse loudly (nonzero exit); with elastic=true +
       tpu_reshard_on_resume=true it completes, bit-identical to a second
       fresh 4-device resume from the same epoch.

    Prints ONE JSON line; exit 0 iff every arm passed. `value` is the
    number of arms passed; LGBM_TPU_CHAOS_DIST_OUT banks the payload
    (fleet_mttr_s / detect_p50_ms / detect_p99_ms / shed_epochs feed the
    ledger under the |chaos_dist= comparability key)."""
    from lightgbm_tpu.utils.hermetic import force_cpu_backend
    force_cpu_backend()
    import shutil
    import socket
    import statistics
    import tempfile
    import threading
    import time

    from lightgbm_tpu.robustness import distributed as gdist
    from lightgbm_tpu.robustness.chaos import FakeKVStore
    from lightgbm_tpu.robustness.retry import PeerLostError
    from lightgbm_tpu.robustness.watchdog import EXIT_COMM_LOST

    fast = os.environ.get("LGBM_TPU_CHAOS_DIST_FAST", "") == "1"
    seed = int(os.environ.get("LGBM_TPU_CHAOS_SEED", "1234"))
    work = tempfile.mkdtemp(prefix="lgbm_bench_chaosdist_")
    repo = os.path.dirname(os.path.abspath(__file__))
    out = {"metric": "chaos_dist",
           "chaos_dist": "gang2|kill9+flap+lease+manifest+shrink",
           "platform": "cpu", "seed": seed, "fast": fast, "arms": {}}
    ok, err = True, []

    def arm(name, fn):
        nonlocal ok
        try:
            out["arms"][name] = dict(fn() or {}, ok=True)
        except Exception as e:            # noqa: BLE001 — fail the arm
            ok = False
            out["arms"][name] = {"ok": False,
                                 "error": f"{type(e).__name__}: {e}"[:300]}
            err.append(f"{name}: {type(e).__name__}: {e}")

    # ---- arm 1: heartbeat lease expiry -> typed PeerLostError ----------
    def arm_lease():
        trials = 8 if fast else 40
        lease_s = 0.05
        lat = []
        for _t in range(trials):
            kv = FakeKVStore()
            me = gdist.HeartbeatLease(client=kv, rank=0, world=2,
                                      lease_timeout_s=lease_s,
                                      interval_s=0.0, probe_timeout_ms=20)
            peer = gdist.HeartbeatLease(client=kv, rank=1, world=2,
                                        lease_timeout_s=lease_s,
                                        interval_s=0.0, probe_timeout_ms=20)
            me.beat(force=True)
            peer.beat(force=True)          # rank 1's one and only beat
            me.check_peers()               # observe the live lease once
            t_dead = time.monotonic()      # ... then rank 1 'dies' NOW
            deadline = t_dead + 5.0
            named = None
            while time.monotonic() < deadline:
                try:
                    me.beat()
                    me.check_peers()
                except PeerLostError as e:
                    named = e.rank
                    lat.append((time.monotonic() - t_dead) * 1000.0)
                    break
                time.sleep(0.002)
            if named != 1:
                raise RuntimeError(
                    f"trial {_t}: dead peer not detected as rank 1 within "
                    f"5s (got {named!r}) — lease_timeout_s={lease_s}")
        lat.sort()
        p50 = statistics.median(lat)
        p99 = lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))]
        out["detect_p50_ms"] = round(p50, 2)
        out["detect_p99_ms"] = round(p99, 2)
        return {"trials": trials, "lease_timeout_ms": lease_s * 1e3,
                "detect_p50_ms": round(p50, 2),
                "detect_p99_ms": round(p99, 2)}

    # ---- arm 2: KV flap during init -> reset + retry, join on 2nd ------
    def arm_kv_flap():
        import jax

        from lightgbm_tpu.config import Config
        from lightgbm_tpu.parallel import comm as _comm
        if _comm.distributed_client() is not None:
            raise RuntimeError("bench process unexpectedly has a live "
                               "distributed client")
        calls = {"init": 0, "reset": 0}
        real_init = jax.distributed.initialize
        real_shutdown = jax.distributed.shutdown

        def flap_init(**kw):
            calls["init"] += 1
            if calls["init"] == 1:
                raise RuntimeError("KV flap: coordination service dropped "
                                   "the handshake mid-connect")

        def count_shutdown():
            calls["reset"] += 1

        old_base = os.environ.get("LGBM_TPU_COMM_BACKOFF_BASE")
        os.environ["LGBM_TPU_COMM_BACKOFF_BASE"] = "0.01"
        jax.distributed.initialize = flap_init
        jax.distributed.shutdown = count_shutdown
        try:
            cfg = Config.from_params(dict(
                num_machines=2,
                machines="127.0.0.1:12601,127.0.0.1:12602",
                local_listen_port=12601, time_out=1))
            _comm.init_distributed(cfg)
        finally:
            jax.distributed.initialize = real_init
            jax.distributed.shutdown = real_shutdown
            if old_base is None:
                os.environ.pop("LGBM_TPU_COMM_BACKOFF_BASE", None)
            else:
                os.environ["LGBM_TPU_COMM_BACKOFF_BASE"] = old_base
        if calls["init"] != 2 or calls["reset"] != 1:
            raise RuntimeError(
                f"expected attempt-1 failure to reset partial init and "
                f"attempt 2 to join: init calls={calls['init']}, "
                f"partial-init resets={calls['reset']}")
        return {"init_attempts": calls["init"],
                "partial_init_resets": calls["reset"]}

    # ---- arm 3: manifest/shard mismatch -> gang falls back TOGETHER ----
    def arm_manifest():
        kv = FakeKVStore(world=2)
        gang_dir = os.path.join(work, "gang_manifest")
        failures = []

        def one_rank(r, fn, slot, results):
            try:
                results[slot] = fn(gdist.GangCheckpointCoordinator(
                    gang_dir, client=kv, rank=r, world=2,
                    timeout_ms=30_000))
            except Exception as e:        # noqa: BLE001 — collected below
                failures.append(f"rank {r}: {type(e).__name__}: {e}")

        def gang(fn):
            results = [None, None]
            ts = [threading.Thread(target=one_rank, args=(r, fn, r, results))
                  for r in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            if failures:
                raise RuntimeError("; ".join(failures))
            return results

        def save_two(co):
            for it in (2, 4):
                co.save({"iteration": it,
                         "config_fingerprint": "bench-chaos-dist",
                         "config": {"tree_learner": "data"},
                         "state": {"n_devices": 1, "tree_learner": "data"},
                         "model": list(range(200))})
            return co.local_verified_epochs()

        epochs = gang(save_two)
        if epochs != [[1, 2], [1, 2]]:
            raise RuntimeError(f"gang banked {epochs}, wanted two epochs "
                               f"verified on both ranks")
        # rot rank 1's NEWEST shard: the manifest's CRC no longer matches
        bad = os.path.join(gang_dir, "shard_0000000002_r0001.pkl")
        raw = bytearray(open(bad, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(bad, "wb").write(bytes(raw))
        shards = gang(lambda co: co.resolve_resume())
        want = [os.path.join(gang_dir, f"shard_0000000001_r{r:04d}.pkl")
                for r in range(2)]
        if shards != want:
            raise RuntimeError(
                f"gang did not fall back a FULL epoch together: resolved "
                f"{[os.path.basename(s) if s else s for s in shards]}")
        out["shed_epochs"] = 1             # epoch 2 known, epoch 1 resumed
        # the --verify CLI on a dir holding ONLY the disagreeing epoch
        # must exit 2 (manifest present, shard set does not verify)
        bad_dir = os.path.join(work, "gang_bad_only")
        os.makedirs(bad_dir)
        for name in ("manifest_0000000002.json", "shard_0000000002_r0000.pkl",
                     "shard_0000000002_r0001.pkl"):
            shutil.copy(os.path.join(gang_dir, name),
                        os.path.join(bad_dir, name))
        rc = subprocess.call(
            [sys.executable, "-m", "lightgbm_tpu.robustness.checkpoint",
             "--verify", bad_dir],
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        if rc != 2:
            raise RuntimeError(f"checkpoint --verify on the disagreeing "
                               f"epoch exited {rc}, wanted 2")
        return {"shed_epochs": 1, "verify_rc_on_bad_epoch": rc}

    # ---------------------------------------------------- subprocess plumbing
    def _free_ports(n):
        socks, ports = [], []
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        return ports

    child_env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                     XLA_FLAGS="--xla_force_host_platform_device_count=1")
    child_py = os.path.join(repo, "tests", "chaos_dist_child.py")

    # ---- arm 4: kill -9 one rank mid-epoch -> 145 + relaunch + MTTR ----
    def arm_kill9():
        from lightgbm_tpu.robustness.supervisor import FleetSupervisor

        def gang_clean(model, ck_dir):
            ports = _free_ports(2)
            procs = [subprocess.Popen(
                [sys.executable, child_py, f"rank={r}", "world=2",
                 "ports=" + ",".join(map(str, ports)),
                 f"checkpoint_dir={ck_dir}", f"out_model={model}",
                 "rounds=12"], env=child_env, cwd=work)
                for r in range(2)]
            rcs = [p.wait(timeout=600) for p in procs]
            if rcs != [0, 0]:
                raise RuntimeError(f"fault-free gang run failed: {rcs}")

        clean_model = os.path.join(work, "gang_clean.txt")
        gang_clean(clean_model, os.path.join(work, "ck_gang_clean"))

        ck_kill = os.path.join(work, "ck_gang_kill")
        kill_model = os.path.join(work, "gang_kill9.txt")
        template = ["rank={rank}", "world={world}",
                    f"checkpoint_dir={ck_kill}", f"out_model={kill_model}",
                    "rounds=12", "kill_rank=1", "kill_after_manifests=2",
                    f"kill_marker={os.path.join(work, 'killed.marker')}"]

        def pre_launch(world, generation):
            return ["ports=" + ",".join(map(str, _free_ports(world)))]

        def spawn(argv):
            return subprocess.Popen([sys.executable, child_py] + list(argv),
                                    env=child_env, cwd=work)

        fleet = FleetSupervisor(template, 2, seed=seed, max_restarts=3,
                                backoff_base_s=0.1, backoff_max_s=1.0,
                                reap_grace_s=60.0, pre_launch_fn=pre_launch,
                                spawn_fn=spawn)
        rc = fleet.run()
        rep = fleet.report()
        if rc != 0 or fleet.restarts < 1:
            raise RuntimeError(f"fleet did not recover: rc={rc} "
                               f"report={rep}")
        codes = fleet.gang_exit_codes[0]     # int rank keys (report() strs)
        if codes.get(1) != -9:
            raise RuntimeError(f"rank 1 was not the kill -9 culprit: "
                               f"{codes}")
        if codes.get(0) != EXIT_COMM_LOST:
            raise RuntimeError(
                f"surviving rank 0 exited {codes.get(0)}, wanted "
                f"{EXIT_COMM_LOST} (typed comm loss naming the peer)")
        identical = open(kill_model).read() == open(clean_model).read()
        if not identical:
            raise RuntimeError("recovered gang model differs from the "
                               "fault-free gang run")
        mttr = rep["recovery_seconds"][0] if rep["recovery_seconds"] \
            else None
        if mttr is None:
            raise RuntimeError(f"fleet MTTR was not measured: {rep}")
        out["fleet_mttr_s"] = round(mttr, 2)
        return {"gang_exit_codes": {str(k): v for k, v in codes.items()},
                "restarts": rep["restarts"],
                "fleet_mttr_s": round(mttr, 2),
                "identical_to_clean": identical}

    # ---- arm 5: elastic 8->4 shrink ------------------------------------
    def arm_shrink():
        n_rows = 4000
        X, y = _higgs_like(n_rows)
        data = os.path.join(work, "shrink_train.csv")
        with open(data, "w") as fh:
            for i in range(n_rows):
                fh.write(",".join([f"{y[i]:.6g}"]
                                  + [f"{v:.6g}" for v in X[i]]) + "\n")
        ck = os.path.join(work, "ck_shrink")

        def cli(extra, devices, model):
            env = dict(child_env,
                       XLA_FLAGS="--xla_force_host_platform_device_count="
                                 + str(devices))
            # tree_learner=data so the mesh really spans the forced device
            # count — serial would train on ONE device at any count and
            # the snapshot would never record the 8-device layout the
            # guard must refuse
            argv = [f"data={data}", "task=train", "objective=binary",
                    "tree_learner=data", "num_leaves=31", "max_bin=63",
                    "learning_rate=0.1", "min_data_in_leaf=20",
                    "metric=none", "seed=17", "verbose=-1",
                    f"output_model={model}",
                    f"checkpoint_dir={ck}", "checkpoint_interval=2"] + extra
            return subprocess.call(
                [sys.executable, "-m", "lightgbm_tpu"] + argv,
                env=env, cwd=work)

        half = os.path.join(work, "shrink_half.txt")
        if cli(["num_trees=10"], 8, half) != 0:
            raise RuntimeError("8-device checkpointed run failed")
        refused = cli(["num_trees=20", "resume_from=auto"], 4,
                      os.path.join(work, "shrink_refused.txt"))
        if refused == 0:
            raise RuntimeError(
                "resume at 4 devices WITHOUT tpu_reshard_on_resume "
                "succeeded — the device-count guard is gone")
        ck_oracle = os.path.join(work, "ck_shrink_oracle")
        shutil.copytree(ck, ck_oracle)
        elastic = ["num_trees=20", "resume_from=auto", "elastic=true",
                   "tpu_reshard_on_resume=true"]
        m1 = os.path.join(work, "shrink_elastic.txt")
        m2 = os.path.join(work, "shrink_oracle.txt")
        if cli(elastic, 4, m1) != 0:
            raise RuntimeError("elastic 8->4 resume failed")
        ck_saved, ck2 = ck_oracle, ck
        shutil.rmtree(ck2)
        shutil.copytree(ck_saved, ck2)
        if cli(elastic, 4, m2) != 0:
            raise RuntimeError("oracle 4-device resume failed")
        identical = open(m1).read() == open(m2).read()
        if not identical:
            raise RuntimeError("elastic shrink is not bit-identical to a "
                               "fresh 4-device resume of the same epoch")
        return {"refused_rc_without_reshard": refused,
                "identical_to_fresh_small_resume": identical}

    try:
        arm("lease_expiry", arm_lease)
        arm("kv_flap_init", arm_kv_flap)
        arm("manifest_mismatch", arm_manifest)
        if fast:
            out["arms"]["kill9_rank"] = {"ok": True, "skipped": "fast"}
            out["arms"]["shrink_8to4"] = {"ok": True, "skipped": "fast"}
            # keep the ledger fields comparable in FAST runs: the banked
            # payload is only written by the full matrix (see below)
        else:
            arm("kill9_rank", arm_kill9)
            arm("shrink_8to4", arm_shrink)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    out["value"] = sum(1 for a in out["arms"].values()
                       if a.get("ok") and "skipped" not in a)
    out["unit"] = "arms"
    out["ok"] = ok
    if err:
        out["error"] = "; ".join(err)[:500]
    print(json.dumps(out))
    out_path = os.environ.get("LGBM_TPU_CHAOS_DIST_OUT", "")
    if out_path and not fast:
        from lightgbm_tpu.observability.export import atomic_write_json
        atomic_write_json(out_path, out)
    return 0 if ok else 1


# --------------------------------------------------------------- multichip

def _multichip_child_env(d, platform):
    """Environment for one scaling-point child: on the CPU backend the
    device count is SIMULATED by re-arming --xla_force_host_platform_
    device_count (the same forcing the test harness and dryrun_multichip
    use) and JAX_PLATFORMS=cpu pins the child off the chip; on real chips
    the child sees all devices and the params slice the mesh
    (num_machines). Children place the persistent compile cache themselves
    (utils/cache.resolve_compile_cache), so repeat runs skip the
    per-device-count step compiles."""
    from lightgbm_tpu.utils.hermetic import force_device_count_flags
    env = dict(os.environ)
    if platform == "cpu":
        env["XLA_FLAGS"] = force_device_count_flags(
            env.get("XLA_FLAGS", ""), d)
        env["JAX_PLATFORMS"] = "cpu"
    else:
        # a real-chip child must not inherit a CPU pin (it would measure
        # the host CPU under a platform='tpu' label — select_devices
        # accepts a pinned CPU) or a stale virtual device count
        env.pop("JAX_PLATFORMS", None)
        env["XLA_FLAGS"] = force_device_count_flags(
            env.get("XLA_FLAGS", ""), None)
    return env


def run_multichip_child(argv):
    """`bench.py --multichip-child <json>`: ONE scaling point — train the
    configured strategy over this process's device mesh, measure steady
    throughput under a record-only RecompileGuard, and report analytic vs
    measured (compiled-HLO) collective bytes. Prints one JSON line."""
    cfg = json.loads(argv[argv.index("--multichip-child") + 1])
    from lightgbm_tpu.utils.cache import resolve_compile_cache
    resolve_compile_cache()
    import jax
    import lightgbm_tpu as lgb
    if jax.default_backend() != cfg.get("platform"):
        raise RuntimeError(
            f"scaling point asked for platform={cfg.get('platform')!r} but "
            f"jax runs on {jax.default_backend()!r}")
    from lightgbm_tpu import observability as obs
    from lightgbm_tpu.observability import costs as obs_costs
    obs_costs.configure(enabled=True)    # measured collectives ride the
                                         # compile-time cost capture
    d = int(cfg["devices"])
    rows = int(cfg["rows"])
    params = dict(
        objective="binary", num_leaves=int(cfg.get("num_leaves", 31)),
        max_bin=int(cfg.get("max_bin", 63)), learning_rate=0.1,
        min_data_in_leaf=20, verbose=-1, metric="none",
        tpu_hist_kernel="xla", tree_batch=int(cfg.get("tree_batch", 4)),
        tree_learner=cfg.get("strategy", "data"),
        device="cpu" if cfg.get("platform") == "cpu" else "tpu")
    if cfg.get("platform") != "cpu":
        # real chips: the child sees the full mesh; num_machines slices the
        # first d local devices (parallel/comm.py make_parallel_context).
        # d=1 must be tree_learner=serial — the slice condition is nm > 1,
        # so a data-parallel "d=1" child would silently train on ALL chips
        if d > 1:
            params["num_machines"] = d
        else:
            params["tree_learner"] = "serial"
    X, y = _higgs_like(rows, seed=3)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    g = bst._gbdt
    if g.pctx.num_devices != d:
        # fail LOUDLY: measuring fewer chips than requested would file the
        # point under the wrong device count (and the wrong ledger key)
        raise RuntimeError(
            f"requested {d} device(s) but the mesh resolved to "
            f"{g.pctx.num_devices} — host has too few chips?")
    out = {"requested_devices": d, "rows": rows}
    out.update(g.pctx.describe())
    timings = {}
    el, guard, iters = _timed_update_phase(
        f"mc_{cfg.get('phase', 'point')}_d{d}", bst,
        int(cfg.get("warmup", 2)), int(cfg.get("timed", 4)), timings,
        tree_batch=g.tree_batch)
    tp = rows * iters / el / 1e6
    out["mrow_tree_per_s"] = _round_tp(tp)
    out["per_chip_mrow_tree_per_s"] = _round_tp(
        tp / max(g.pctx.num_devices, 1))
    rep = guard.report()
    out["recompiles_post_warmup"] = rep["post_warmup_cache_misses"]
    out["host_syncs"] = rep["host_syncs"]
    out["tree_batch"] = g.tree_batch
    out["phase_timings"] = timings
    # analytic per-wave estimates (comm.bytes_per_wave.* gauges, published
    # at booster construction) next to the measured compiled-HLO truth
    gauges = obs.snapshot()["gauges"]
    out["analytic_bytes_per_wave"] = {
        k.split("comm.bytes_per_wave.")[-1]: v
        for k, v in gauges.items() if k.startswith("comm.bytes_per_wave.")}
    cost_rep = obs_costs.report(f"train_step.k{g.tree_batch}") or {}
    coll = cost_rep.get("collectives")
    if coll:
        out["measured_collectives"] = coll
        out["measured_wire_bytes"] = obs_costs.collective_wire_bytes(
            coll, g.pctx.num_devices)
    print(json.dumps(out))
    return 0


# analytic collective names -> the HLO op kind they lower to, for the
# measured-vs-analytic ratio (psum -> all-reduce, psum_scatter ->
# reduce-scatter, the candidate sync -> all-gather)
_ANALYTIC_OP_OF = {
    "psum_root_scalars": "all-reduce", "psum_votes": "all-reduce",
    "psum_gain_ranks": "all-reduce", "psum_selected_hist": "all-reduce",
    "psum_scatter_hist": "reduce-scatter",
    "allgather_splits": "all-gather",
}


def run_multichip(argv):
    """`bench.py --multichip`: measured multi-chip training — weak- and
    strong-scaling phases over a device-count ladder, one child process per
    point, run one after another (simulated devices via
    --xla_force_host_platform_device_count on the CPU backend, real chips
    otherwise). This parent never initialises a jax backend — it imports
    only backend-free helpers — so each child in turn can take the chips.
    Per-phase watchdogs like the main bench's. Emits ONE
    MULTICHIP json line with Mrow-tree/s per chip, scaling efficiency,
    measured (compiled-HLO) vs analytic collective bytes, and per-point
    recompile/host-sync counts; LGBM_TPU_MULTICHIP_OUT also writes it to a
    file. Knobs: LGBM_TPU_MULTICHIP_{PLATFORM,DEVICES,ROWS_PER_DEV,ROWS,
    TIMED_ITERS,TIMEOUT,LEARNER}."""
    budget = int(os.environ.get("LGBM_TPU_MULTICHIP_TIMEOUT", "2700"))
    t0 = time.time()

    def deadline():
        return budget - (time.time() - t0) - 20

    def on_alarm(signum, frame):
        raise BenchTimeout(f"multichip bench exceeded {budget}s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(budget)

    platform = os.environ.get("LGBM_TPU_MULTICHIP_PLATFORM", "cpu")
    cpu = platform == "cpu"
    dev_counts = sorted({int(x) for x in os.environ.get(
        "LGBM_TPU_MULTICHIP_DEVICES", "1,2,4,8").split(",") if x.strip()})
    rows_per_dev = int(os.environ.get(
        "LGBM_TPU_MULTICHIP_ROWS_PER_DEV",
        "16000" if cpu else "1312500"))       # tpu: 10.5M/8 per chip
    strong_rows = int(os.environ.get(
        "LGBM_TPU_MULTICHIP_ROWS", "64000" if cpu else "2100000"))
    timed = int(os.environ.get("LGBM_TPU_MULTICHIP_TIMED_ITERS", "4"))
    learner = os.environ.get("LGBM_TPU_MULTICHIP_LEARNER", "data")
    max_d = max(dev_counts)

    result = {
        "metric": "multichip_scaling",
        "unit": "Mrow-tree/s/chip",
        "platform": platform,
        "simulated": cpu,
        "tree_learner": learner,
        "n_devices": max_d,
        "device_counts": dev_counts,
        "rows_per_device": rows_per_dev,
        "rows_strong": strong_rows,
        "weak": [],
        "strong": [],
    }
    children = {}                      # (phase, d) -> full child payload

    def run_child(phase, d, rows, strategy=learner):
        cfg = {"devices": d, "rows": rows, "strategy": strategy,
               "platform": platform, "phase": phase, "timed": timed,
               "warmup": 2}
        cmd = [sys.executable, os.path.abspath(__file__),
               "--multichip-child", json.dumps(cfg)]
        timeout = int(max(60, min(deadline() - 30, 900)))
        with _phase_watchdog(f"{phase}_d{d}", timeout + 30):
            r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                               text=True,
                               env=_multichip_child_env(d, platform))
        if r.returncode != 0 or not r.stdout.strip():
            raise RuntimeError(
                f"child {phase} d={d} rc={r.returncode}: "
                f"{(r.stderr or 'no output')[-300:]}")
        return json.loads(r.stdout.strip().splitlines()[-1])

    # the whole measurement section degrades on a blown global budget: the
    # ONE-JSON-line contract holds on every path (a BenchTimeout escaping
    # here would kill the process with no MULTICHIP json at all)
    result["strategy_points"] = {}
    try:
        for phase, rows_of in (("weak", lambda d: rows_per_dev * d),
                               ("strong", lambda d: strong_rows)):
            for d in dev_counts:
                if deadline() < 90:
                    result[phase].append({"d": d,
                                          "error": "budget exhausted"})
                    continue
                try:
                    child = run_child(phase, d, rows_of(d))
                    children[(phase, d)] = child
                    result[phase].append({
                        "d": child["n_devices"], "rows": child["rows"],
                        "strategy": child["strategy"],
                        "mesh_axis": child["mesh_axis"],
                        "mrow_tree_per_s": child["mrow_tree_per_s"],
                        "per_chip": child["per_chip_mrow_tree_per_s"],
                        "recompiles_post_warmup":
                            child["recompiles_post_warmup"],
                        "host_syncs": child["host_syncs"],
                    })
                except BenchTimeout:
                    raise
                except Exception as e:                       # noqa: BLE001
                    traceback.print_exc(file=sys.stderr)
                    result[phase].append({"d": d, "error": str(e)[:200]})
        # one smoke point per remaining strategy at the full mesh (the
        # parity suite trains them for correctness; this records their
        # throughput)
        for strat in ("feature", "voting"):
            if strat == learner or deadline() < 120:
                continue
            try:
                child = run_child("strategy", max_d, rows_per_dev * max_d,
                                  strategy=strat)
                result["strategy_points"][strat] = {
                    "d": child["n_devices"],
                    "mrow_tree_per_s": child["mrow_tree_per_s"],
                    "per_chip": child["per_chip_mrow_tree_per_s"],
                    "recompiles_post_warmup":
                        child["recompiles_post_warmup"],
                }
            except BenchTimeout:
                raise
            except Exception as e:                           # noqa: BLE001
                result["strategy_points"][strat] = {"error": str(e)[:200]}
    except BenchTimeout as e:
        result["error"] = str(e)[:200]

    def _tp(phase, d):
        for p in result[phase]:
            if p.get("d") == d and "mrow_tree_per_s" in p:
                return p["mrow_tree_per_s"]
        return None

    # headline device count = the largest MEASURED mesh (children fail
    # loudly on a requested/actual mismatch, so requested == actual for
    # every recorded point; a short-chip host simply tops out lower)
    measured_d = [p["d"] for p in result["weak"] + result["strong"]
                  if "mrow_tree_per_s" in p]
    head_d = max(measured_d) if measured_d else max_d
    result["n_devices"] = head_d
    for phase, field in (("weak", "weak_efficiency"),
                         ("strong", "strong_efficiency")):
        t1, td = _tp(phase, 1), _tp(phase, head_d)
        # scaling efficiency = tp(D) / (D * tp(1)) for both phases (weak
        # total rows grow with D, so ideal throughput is D x the 1-chip
        # run either way); per-point efficiencies ride in the series
        if t1 and td:
            result[field] = round(td / (head_d * t1), 3)
            for p in result[phase]:
                if p.get("mrow_tree_per_s"):
                    p["efficiency"] = round(
                        p["mrow_tree_per_s"] / (p["d"] * t1), 3)
    head = children.get(("weak", head_d))
    if head:
        result["per_chip_mrow_tree_per_s"] = \
            head["per_chip_mrow_tree_per_s"]
        analytic = head.get("analytic_bytes_per_wave") or {}
        measured = head.get("measured_wire_bytes") or {}
        cb = {"analytic_per_wave": analytic,
              "measured_hlo_output": head.get("measured_collectives"),
              "measured_wire_per_step": measured}
        # like-for-like ratio: analytic names grouped by the HLO op they
        # lower to, judged against the wire-byte model — the satellite
        # 'fix any estimate off by >2x' check reads this field
        by_op = {}
        for name, nbytes in analytic.items():
            op = _ANALYTIC_OP_OF.get(name)
            if op:
                by_op[op] = by_op.get(op, 0) + nbytes
        ratios = {}
        for op, abytes in sorted(by_op.items()):
            m = measured.get(op)
            if m and abytes:
                ratios[op] = round(m / abytes, 3)
        cb["measured_over_analytic"] = ratios
        result["collective_bytes"] = cb
    signal.alarm(0)
    multi_ok = [p for p in result["weak"] + result["strong"]
                if p.get("d", 0) > 1 and "mrow_tree_per_s" in p]
    result["ok"] = bool(multi_ok
                        and result.get("per_chip_mrow_tree_per_s"))
    result["elapsed_s"] = round(time.time() - t0, 1)
    line = json.dumps(result)
    out_path = os.environ.get("LGBM_TPU_MULTICHIP_OUT")
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(json.dumps(result, indent=1, sort_keys=True) + "\n")
        os.replace(tmp, out_path)
    print(line)
    return 0 if result["ok"] else 1


def run_compare(argv):
    """`bench.py --compare [result.json]`: flag perf regressions of a bench
    result against the checked-in history (observability/ledger.py).

    The candidate defaults to the newest committed ``BENCH_r*.json`` (its
    own entry is excluded from the best-known computation, so re-judging
    history never self-compares). Checks: throughput vs best-known for the
    same platform/rows, post-warm-up recompiles, headline host syncs, peak
    HBM, and compiled cost-model drift. Prints ONE JSON line; exit 0 clean,
    2 on any regression — the `make bench-diff` / `make verify` gate. This
    is a pure file comparison: no backend, no training, so it runs anywhere
    in milliseconds."""
    import glob as _glob

    from lightgbm_tpu.observability import ledger as perf_ledger
    repo = os.path.dirname(os.path.abspath(__file__))
    idx = argv.index("--compare")
    explicit = [a for a in argv[idx + 1:] if not a.startswith("-")]
    path = explicit[0] if explicit else None
    entries = perf_ledger.load_history(repo)
    if path is None:
        hist = sorted(_glob.glob(os.path.join(repo, "BENCH_r*.json")))
        path = hist[-1] if hist else None
    if path is None:
        # no headline history yet: nothing to judge the headline against;
        # the banked companion results below are still judged
        out = {"metric": "perf_ledger_compare", "candidate": None,
               "problems": [], "ok": True,
               "notes": ["no BENCH_r*.json headline history — headline "
                         "not judged"]}
        problems = []
    else:
        payload = perf_ledger.payload_of(path)
        problems, notes = perf_ledger.compare(
            payload or {}, entries, exclude_source=os.path.basename(path))
        out = {"metric": "perf_ledger_compare",
               "candidate": os.path.basename(path),
               "value": (payload or {}).get("value"),
               "platform": (payload or {}).get("platform"),
               "rows": (payload or {}).get("rows"),
               "problems": problems, "notes": notes,
               "ok": not problems}
    if explicit == []:
        # default mode also judges the newest MEASURED multichip report
        # (dry-run wrappers from rounds 1-5 carry no numbers and are
        # skipped): per-chip throughput regressions fail make bench-diff
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "MULTICHIP_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "multichip_scaling":
                continue
            mp, mn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["multichip"] = {"candidate": os.path.basename(p),
                                "value": pl.get("per_chip_mrow_tree_per_s"),
                                "problems": mp, "notes": mn, "ok": not mp}
            problems = problems + mp
            break
        # ... and the newest banked STREAM result (bench.py --stream):
        # residency=stream keys it into its own comparability class, so a
        # streamed throughput regression fails here without ever being
        # judged against device-resident numbers
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "STREAM_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("residency") != "stream":
                continue
            sp, sn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["stream"] = {"candidate": os.path.basename(p),
                             "value": pl.get("value"),
                             "identical_to_resident":
                                 pl.get("identical_to_resident"),
                             "problems": sp, "notes": sn, "ok": not sp}
            problems = problems + sp
            break
        # ... and the newest banked SERVE result (bench.py --serve): the
        # |serve= comparability key plus the p99 floor means a serving
        # rows/s OR tail-latency regression fails here without ever being
        # judged against a training-throughput number
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "SERVE_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "serve_bench":
                continue
            vp, vn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["serve"] = {"candidate": os.path.basename(p),
                            "value": pl.get("value"),
                            "p99_ms": pl.get("p99_ms"),
                            "identical_to_train_predict":
                                pl.get("identical_to_train_predict"),
                            "problems": vp, "notes": vn, "ok": not vp}
            problems = problems + vp
            break
        # ... and the newest banked SPARSE result (bench.py --sparse): the
        # |bundle= comparability key means the bundle-space arm is only
        # ever judged against bundle-space history — a sparse-throughput
        # regression of the native EFB representation fails here without
        # touching dense or legacy-arm numbers
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "SPARSE_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "sparse_train_throughput":
                continue
            bp, bn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["sparse"] = {"candidate": os.path.basename(p),
                             "value": pl.get("value"),
                             "bundle": pl.get("bundle"),
                             "noefb_mrow_tree_per_s":
                                 pl.get("noefb_mrow_tree_per_s"),
                             "problems": bp, "notes": bn, "ok": not bp}
            problems = problems + bp
            break
        # ... and the newest banked LINEAR result (bench.py --linear): the
        # |linear= comparability key means the ridge-solve workload is
        # only judged against linear-leaf history — a fit-leg throughput
        # regression fails here without touching constant-leaf numbers
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "LINEAR_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "linear_train_throughput":
                continue
            lp, lnn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["linear"] = {"candidate": os.path.basename(p),
                             "value": pl.get("value"),
                             "accuracy_gain_frac":
                                 pl.get("accuracy_gain_frac"),
                             "identical_to_serving":
                                 pl.get("identical_to_serving"),
                             "problems": lp, "notes": lnn, "ok": not lp}
            problems = problems + lp
            break
        # ... and the newest banked INGEST result (bench.py --ingest): the
        # |ingest= comparability key means the device-binning rows/s floor
        # only judges ingest history, and the bit-identity flag is a hard
        # gate — a device binning that drifts from the host oracle by one
        # code fails make bench-diff regardless of throughput
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "INGEST_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "ingest_throughput":
                continue
            ip, inn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["ingest"] = {"candidate": os.path.basename(p),
                             "value": pl.get("value"),
                             "device_vs_host": pl.get("device_vs_host"),
                             "identical_to_host":
                                 pl.get("identical_to_host"),
                             "problems": ip, "notes": inn, "ok": not ip}
            problems = problems + ip
            break
        # ... and the newest banked SERVE_CHAOS result (bench.py
        # --serve-chaos): the |serve_chaos= comparability key gates the
        # shed-rate ceiling and p99-under-overload, so a serving-
        # resilience regression fails here without ever being judged
        # against fault-free serving numbers
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "SERVE_CHAOS_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "serve_chaos":
                continue
            cp, cn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["serve_chaos"] = {"candidate": os.path.basename(p),
                                  "value": pl.get("value"),
                                  "shed_rate": pl.get("shed_rate"),
                                  "p99_ms": pl.get("p99_ms"),
                                  "problems": cp, "notes": cn,
                                  "ok": not cp}
            problems = problems + cp
            break
        # ... and the newest banked CHAOS_DIST result (bench.py
        # --chaos-dist): the |chaos_dist= comparability key gates fleet
        # MTTR, peer-loss detection latency, and shed-epoch regressions
        # against distributed-chaos history only
        for p in reversed(sorted(
                _glob.glob(os.path.join(repo, "CHAOS_DIST_r*.json")))):
            pl = perf_ledger.payload_of(p)
            if not pl or pl.get("metric") != "chaos_dist":
                continue
            dp, dn = perf_ledger.compare(
                pl, entries, exclude_source=os.path.basename(p))
            out["chaos_dist"] = {"candidate": os.path.basename(p),
                                 "value": pl.get("value"),
                                 "fleet_mttr_s": pl.get("fleet_mttr_s"),
                                 "detect_p99_ms": pl.get("detect_p99_ms"),
                                 "shed_epochs": pl.get("shed_epochs"),
                                 "problems": dp, "notes": dn,
                                 "ok": not dp}
            problems = problems + dp
            break
    out["problems"] = problems
    out["ok"] = not problems
    print(json.dumps(out))
    return 0 if not problems else 2


if __name__ == "__main__":
    if "--sparse" in sys.argv:
        print(json.dumps(run_sparse_phase()))
    elif "--smoke" in sys.argv:
        sys.exit(run_smoke())
    elif "--stream" in sys.argv:
        sys.exit(run_stream(sys.argv))
    elif "--ingest" in sys.argv:
        sys.exit(run_ingest(sys.argv))
    elif "--linear" in sys.argv:
        sys.exit(run_linear(sys.argv))
    elif "--serve-chaos" in sys.argv:
        sys.exit(run_serve_chaos(sys.argv))
    elif "--serve" in sys.argv:
        sys.exit(run_serve(sys.argv))
    elif "--chaos-dist" in sys.argv:
        sys.exit(run_chaos_dist(sys.argv))
    elif "--chaos" in sys.argv:
        sys.exit(run_chaos(sys.argv))
    elif "--compare" in sys.argv:
        sys.exit(run_compare(sys.argv))
    elif "--multichip-child" in sys.argv:
        sys.exit(run_multichip_child(sys.argv))
    elif "--multichip" in sys.argv:
        sys.exit(run_multichip(sys.argv))
    else:
        sys.exit(main())
