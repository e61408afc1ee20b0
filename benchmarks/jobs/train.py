"""The ``train`` job: one booster, built as ``engine.train`` builds it, driven
through ``Booster.update`` one dispatch after another.

Set-up (all of it inside ``setup_s``): the data from the seed, on the host;
``lgb.Dataset`` and ``lgb.Booster`` exactly as a user makes them (bin finding
on a row sample, device ingest); ``warmup_dispatches`` updates, which compile
or load the step. The SAME booster then goes to the window. After each of the
first ``followed_trees`` set-up dispatches the resident score is read at the
sample rows, for the reference to compare.

Window: ``update`` then ``block_until_ready``, again and again until
``--seconds`` have passed at the end of a dispatch. Nothing may compile or
load a program inside it: the run dies if jax.monitoring saw one.

With ``--trace 1`` one more dispatch is made BEFORE the window under the
profiler; it is reduced in this process (lib/xplane.py) and only the reduced
numbers leave it.

After the window: the device's peak memory is read; the resident score of
every row comes to the host and the same booster makes ONE more dispatch, the
steady tree (what a dispatch of the window produces, from a score the
reference can start at); the trees come to the host, ``Booster.predict``
walks the followed trees on the sample rows, the booster is freed, and only
then the plain reference values the followed trees and the steady tree over
all rows (lib/reference.py, lib/compare.py).
"""
import gc
import glob
import os
import shutil
import time

import numpy as np

from lib import compare, datagen, peaks, reference, work, xplane
from lib.compile_meter import CompileMeter

TREE_KEYS = ("split_feature", "threshold", "decision_type", "left_child",
             "right_child", "split_gain", "internal_value", "internal_count",
             "leaf_value", "leaf_count")


def tree_dict(tree) -> dict:
    """A program ``Tree`` as plain arrays: all the reference ever sees."""
    if tree.cat_boundaries is not None or tree.is_linear:
        raise ValueError("categorical or linear tree: outside this reference")
    d = {k: np.asarray(getattr(tree, k)) for k in TREE_KEYS}
    d["num_leaves"] = int(tree.num_leaves)
    return d


def build(ctx: dict, train_set):
    """The booster as ``engine.train`` makes it (engine.py: cache placement,
    then ``Booster(params, train_set)``), and its GBDT."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import resolve_compile_cache
    resolve_compile_cache()
    bst = lgb.Booster(params=dict(ctx["config"]["params"]), train_set=train_set)
    return bst, bst._gbdt


def dispatch(bst, gbdt) -> float:
    """One ``Booster.update``, ended by a block on the resident score."""
    import jax
    t = time.perf_counter()
    bst.update()
    jax.block_until_ready(gbdt.score)
    return time.perf_counter() - t


def score_at(gbdt, idx_dev) -> np.ndarray:
    import jax
    return np.asarray(jax.device_get(gbdt.score[0][idx_dev]), np.float64)


def memory_peak() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = int(max(s.get("peak_bytes_in_use", 0) for s in stats))
    if not peak:
        raise RuntimeError("the device reports no peak_bytes_in_use")
    return peak


def refer(cfg: dict, traffic: dict, X, y, valued, score_before, sample, seed, **mode):
    """The plain reference over the valued trees: the followed ones from the
    initial score on, the steady tree (the last) from ``score_before``. With
    a ``mode`` (precision, rows_kept) it is the control or a fault, and scans
    no nodes."""
    sem = cfg["semantics"]
    scan = None if mode else dict(traffic["scan"], seed=seed,
                                  min_side=int(cfg["params"]["min_data_in_leaf"]))
    return reference.follow(X, y, valued, learning_rate=float(sem["learning_rate"]),
                            lambda_l2=float(sem["lambda_l2"]),
                            init_score=float(sem["init_score"]), sample=sample,
                            restart={len(valued) - 1: score_before}, scan=scan, **mode)


def traced_dispatch(ctx, bst, gbdt) -> dict:
    """One dispatch under the profiler, reduced here. The xplane stays in a
    scratch directory inside the checkout and is deleted."""
    import jax
    out = os.path.join(ctx["here"], ".trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                bst.update()
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(gbdt.score)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    size = os.path.getsize(path)
    t = time.perf_counter()
    recorded = xplane.device_events(path)
    reduced = xplane.reduce_events(recorded)
    ctx["log"](f"trace: {size / 2**20:.1f} MiB xplane, {reduced.get('n_events')} device "
               f"events, reduced in {time.perf_counter() - t:.1f}s; busy "
               f"{reduced.get('busy_s')} of {reduced.get('window_s')} s; classes "
               f"{reduced.get('class_s')} events {reduced.get('class_events')}")
    for name, seconds in reduced.get("ops", [])[:25]:
        ctx["log"](f"trace op {seconds:10.4f}s  {name}")
    for name, seconds in reduced.get("gaps", [])[:10]:
        ctx["log"](f"trace gap {seconds:10.4f}s  {name}")
    shutil.rmtree(out, ignore_errors=True)
    if not reduced.get("devices"):
        raise RuntimeError("the trace holds no device operation")
    return reduced


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    rows = int(cfg["data"]["rows"])
    features = datagen.num_features(cfg["data"])
    followed = int(traffic["followed_trees"])
    warmup = int(traffic["warmup_dispatches"])
    if warmup < followed:
        raise ValueError("the reference follows set-up's dispatches: "
                         "warmup_dispatches must be >= followed_trees")
    # an unknown device is an error before anything runs, not after
    floor_s, bound_by = work.root_pass_floor_s(
        rows, features, int(cfg["params"]["max_bin"]),
        peaks.peaks_for(ctx["device"]["kind"]))
    meter = CompileMeter()

    # ---------------------------------------------------------------- set-up
    X, y = datagen.generate(cfg["data"], rows, ctx["seed"])
    t_gen = time.time() - ctx["t0"]
    log(f"data: {rows} x {features} float32 from seed {ctx['seed']}, "
        f"{float(y.mean()):.4f} positive")
    sample = np.sort(np.random.default_rng([ctx["seed"], 1]).choice(
        rows, size=min(int(traffic["sample_rows"]), rows), replace=False))
    X_sample, y_sample = X[sample], y[sample]      # fancy indexing copies
    # the user's float32 rows go to lgb.Dataset, which keeps its own float64
    # copy. Ours is dropped before the booster is built and made again from
    # the seed for the reference: the machine's host memory does not hold the
    # construction's temporaries beside a third copy (PERF.md, set-up debts)
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y)
    del X, y
    gc.collect()
    bst, gbdt = build(ctx, train_set)
    del train_set
    t_built = time.time() - ctx["t0"]
    ingest = dict(gbdt._ingest_report or {})
    log(f"booster: residency={gbdt.residency} kernel={gbdt.spec.hist_kernel} "
        f"slots={gbdt.spec.hist_slots} chunk={gbdt.spec.chunk_rows}; ingest {ingest}")
    if gbdt.residency != cfg["expect"]["residency"]:
        raise RuntimeError(f"residency {gbdt.residency!r}, the cell is sized for "
                           f"{cfg['expect']['residency']!r}")
    idx_dev = jnp.asarray(sample.astype(np.int32))
    warm_s, step_scores = [], []
    for i in range(warmup):
        warm_s.append(dispatch(bst, gbdt))
        if i < followed:
            step_scores.append(score_at(gbdt, idx_dev))
    log(f"warm-up dispatches {[round(s, 2) for s in warm_s]} s; compile so far "
        f"{meter.mark()}")
    trace = traced_dispatch(ctx, bst, gbdt) if ctx["trace"] else None
    setup = meter.mark()
    setup_s = time.time() - ctx["t0"]

    # ---------------------------------------------------------------- window
    durations = []
    t_start = time.perf_counter()
    while True:
        durations.append(dispatch(bst, gbdt))
        elapsed = time.perf_counter() - t_start
        if elapsed >= ctx["seconds"]:
            break
    inside = meter.since(setup)
    if any(inside.values()):
        raise RuntimeError(f"a program compiled or loaded inside the window: {inside}")
    peak = memory_peak()
    trees_done = len(durations)
    rate = rows * trees_done / elapsed / 1e6
    log(f"window: {trees_done} dispatches in {elapsed:.3f}s = {rate:.4f} Mrow-tree/s; "
        f"dispatch seconds {[round(d, 3) for d in durations]}; HBM peak "
        f"{peak / 2**30:.3f} GiB")

    # ------------------------------------------- what the timed path produced
    # the steady tree: one more dispatch of the same booster, from a score
    # the reference can start at (all rows, as the program holds it)
    score_before = np.asarray(jax.device_get(gbdt.score[0]))[:rows]
    steady_s = dispatch(bst, gbdt)
    step_scores.append(score_at(gbdt, idx_dev))
    final_score = step_scores[-1]
    bst._ensure_finalized()
    trees = [tree_dict(t) for t in bst.trees]
    init_score = float(bst.init_score_value)
    if abs(init_score - float(cfg["semantics"]["init_score"])) > 1e-12:
        raise RuntimeError(f"init score {init_score}, the configuration states "
                           f"{cfg['semantics']['init_score']}")
    predict_followed = np.asarray(
        bst.predict(X_sample, raw_score=True, num_iteration=followed), np.float64)
    bst.free_dataset()
    del bst, gbdt, idx_dev
    gc.collect()

    # ------------------------------------------------------------- reference
    t_ref = time.perf_counter()
    X, y = datagen.generate(cfg["data"], rows, ctx["seed"])
    if not np.array_equal(X[sample], X_sample):
        raise RuntimeError("the data made again from the seed differ")
    sem = cfg["semantics"]
    valued = trees[:followed] + [trees[-1]]
    ref = refer(cfg, traffic, X, y, valued, score_before, sample, ctx["seed"])
    init = np.full(len(sample), float(sem["init_score"]))
    root_loss, runner_up = reference.root_split_loss(
        X_sample, y_sample, trees[:followed], [init] + ref["sample_score"][:followed - 1],
        float(sem["lambda_l2"]))
    scans = ref["node_scan"]
    program = {"valued": valued, "followed": followed, "step_scores": step_scores,
               "root_split_loss": root_loss,
               "node_split_loss": max(s[0] for s in scans),
               "predict_followed": predict_followed, "final_score": final_score,
               "walk_all": reference.walk(X_sample, trees, float(sem["init_score"])),
               "init_score": float(sem["init_score"])}
    nums = compare.numbers(program, ref, y_sample)
    limits = compare.load_limits(ctx["here"], ctx["cell"]["name"])
    correct, compared = compare.judge(nums, limits)
    leaves = [t["num_leaves"] for t in trees]
    log(f"reference: valued {followed} followed trees and the steady one (tree "
        f"{len(trees)}, {steady_s:.2f}s) over {rows} rows, scanned "
        f"{sum(s[2] for s in scans)} nodes, walked {len(trees)} trees on {len(sample)} "
        f"rows in {time.perf_counter() - t_ref:.1f}s; leaves per tree {leaves}")
    log("all numbers: " + ", ".join(f"{k}={v:.3e}" for k, v in nums.items()))
    unscanned = len(valued) * int(traffic["scan"]["nodes"]) - sum(s[2] for s in scans)
    if unscanned:
        correct = False
        compared["nodes_unscanned"] = {"value": float(unscanned), "limit": 0.0, "ok": False}
    if min(leaves) <= 1:
        correct = False
        compared["unsplit_trees"] = {"value": float(sum(n <= 1 for n in leaves)),
                                     "limit": 0.0, "ok": False}

    return {
        "correct": correct, "attempted": trees_done, "failed": 0,
        "compared": compared, "memory_peak_bytes": peak,
        "end_to_end": {"train_rate": rate, "hbm_peak_gib": peak / 2**30,
                       "setup_s": setup_s},
        # what the per-layer readers read
        "spans": {"ingest_s": ingest.get("seconds"), "compile_s": setup["compile_s"],
                  "dispatch_s": durations, "window_s": elapsed},
        "counters": {"ingest": ingest, "compile": setup, "trees": trees_done},
        "work": {"rows": rows, "features": features, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
        "trace": trace,
        # kept in memory for benchmarks/tests/readings.py; never printed
        "state": {"X": X, "y": y, "sample": sample, "trees": trees, "ref": ref,
                  "program": program, "score_before": score_before},
        "info": {"numbers": nums, "leaves": leaves, "runner_up_feature_loss": runner_up,
                 "runner_up_node_loss": min(s[1] for s in scans), "steady_s": steady_s,
                 "warmup_s": warm_s, "dispatch_s": durations, "window_s": elapsed,
                 "setup_parts_s": {"data": t_gen, "dataset_and_ingest": t_built - t_gen,
                                   "ingest": ingest.get("seconds"),
                                   "warmup": float(sum(warm_s)),
                                   "compile": setup["compile_s"]},
                 "compile": setup, "ingest": ingest, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
    }
