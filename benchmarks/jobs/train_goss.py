"""The ``train`` job for a cell that trains under GOSS (``boosting=goss``):
one booster driven through ``Booster.update`` exactly as ``jobs/train.py``
drives it, through ``train``'s own functions. It differs in four things.

1. **The window opens on sampled trees only.** GOSS draws no sample while
   ``iteration < 1 / learning_rate`` (goss.hpp:134-137), so the traffic's
   ``warmup_dispatches`` has to carry the booster past that; a window of
   unsampled trees would time plain gbdt, and the run dies instead.
2. **The steady tree is a SAMPLED tree**, valued by ``lib/reference_goss.py``
   from the program's score before it and the program's 0/1 inclusion mask
   of that tree: the step's own ``mask`` output, which ``train_one_iter``
   keeps as ``gbdt.bag_mask``, read right after the steady dispatch
   (``train.run`` frees the booster before it calls ``refer``). The followed
   trees are unsampled and ``reference.follow`` values them as in
   ``criteo67-255-train``. Two numbers join the comparison: ``top_missed``
   and ``other_count_gap`` (lib/reference_goss.py).
3. **The floor is the sample's.** ``work.root_floor_s`` is the root pass of
   ``top_k + other_k`` rows, the one pass no GOSS tree can avoid, so
   ``step.mfu_floor``, ``kernels.hist_roofline`` and
   ``kernels.hist_exec_roofline`` cannot pass 100%. ``work.rows`` stays the
   table's rows: ``train_rate`` counts an iteration over the table, as a
   user counts it, and ``kernels.hist_passes_per_tree`` reads passes of the
   TABLE (0.30 for one pass over the sample).
4. ``memory_peak`` and ``refer`` are forwarded, because ``rehearse.py`` and
   ``tests/readings.py`` reach for them on whatever job a cell names;
   ``refer`` takes the steady tree's mask as ``included``, or the last
   run's where a caller that knows no mask leaves it out.
"""
import gc
import importlib.util
import os
import time

import numpy as np

from lib import compare, datagen, peaks, reference, reference_goss, work
from lib.compile_meter import CompileMeter


def _load_train():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    spec = importlib.util.spec_from_file_location("job_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


train = _load_train()
# rehearse.py and the tests put their own in its place on the CPU, which
# keeps no peak_bytes_in_use
memory_peak = train.memory_peak
# the inclusion mask of the last run's steady tree, for a caller of
# ``refer`` that has no way to hand it over (tests/readings.py)
LAST_RUN = {}


def refer(cfg: dict, traffic: dict, X, y, valued, score_before, sample, seed,
          included=None, **mode):
    """The plain reference over the valued trees: the followed ones
    (unsampled) by ``reference.follow`` from the initial score on, the
    steady tree (the last, sampled) by ``reference_goss.value_tree`` from
    ``score_before`` and the program's mask. With a ``mode`` (precision,
    rows_kept, amplified, score_out_of_sample) it is the control or a
    planted fault, and scans no nodes. One result in ``follow``'s form, with
    the sample's numbers under ``sample``."""
    sem, params = cfg["semantics"], cfg["params"]
    included = LAST_RUN["included"] if included is None else included
    scan = None if mode else dict(traffic["scan"], seed=seed,
                                  min_side=int(params["min_data_in_leaf"]))
    followed_mode = {k: v for k, v in mode.items() if k in ("precision", "rows_kept")}
    out = reference.follow(X, y, valued[:-1], learning_rate=float(sem["learning_rate"]),
                           lambda_l2=float(sem["lambda_l2"]),
                           init_score=float(sem["init_score"]), sample=sample,
                           scan=scan, **followed_mode)
    steady = reference_goss.value_tree(
        X, y, valued[-1], score_before, included,
        learning_rate=float(sem["learning_rate"]), lambda_l2=float(sem["lambda_l2"]),
        top_rate=float(params["top_rate"]), other_rate=float(params["other_rate"]),
        sample=sample, scan=scan, **mode)
    for key in ("leaf_value", "leaf_count", "node_count", "gain", "sample_score",
                "node_scan"):
        out[key] = out[key] + steady[key]
    out["sample"] = steady["sample"]
    return out


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    params = cfg["params"]
    rows = int(cfg["data"]["rows"])
    features = datagen.num_features(cfg["data"])
    followed = int(traffic["followed_trees"])
    warmup = int(traffic["warmup_dispatches"])
    # the first iteration GOSS samples at (goss.hpp:134-137)
    first_sampled = int(1.0 / float(params["learning_rate"]))
    if params.get("boosting") != "goss":
        raise ValueError("jobs/train_goss.py drives a boosting=goss configuration")
    if not followed <= first_sampled < warmup:
        raise ValueError(
            f"GOSS samples from iteration {first_sampled} on: the followed trees "
            f"({followed}) have to end before it and the warm-up ({warmup} "
            f"dispatches) after it, or the window would time unsampled trees")
    top_k, other_k, amplify = reference_goss.counts(
        rows, float(params["top_rate"]), float(params["other_rate"]))
    # the pass no GOSS tree can avoid: one root pass over the sample
    floor_s, bound_by = work.root_pass_floor_s(
        top_k + other_k, features, int(params["max_bin"]),
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
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y)
    del X, y
    gc.collect()
    bst, gbdt = train.build(ctx, train_set)
    del train_set
    t_built = time.time() - ctx["t0"]
    ingest = dict(gbdt._ingest_report or {})
    log(f"booster: {type(gbdt).__name__} residency={gbdt.residency} "
        f"kernel={gbdt.spec.hist_kernel} slots={gbdt.spec.hist_slots} "
        f"chunk={gbdt.spec.chunk_rows}; sample {top_k} + {other_k} of {rows} rows, "
        f"amplify {amplify:.6g}; ingest {ingest}")
    if gbdt.residency != cfg["expect"]["residency"]:
        raise RuntimeError(f"residency {gbdt.residency!r}, the cell is sized for "
                           f"{cfg['expect']['residency']!r}")
    idx_dev = jnp.asarray(sample.astype(np.int32))
    warm_s, step_scores = [], []
    for i in range(warmup):
        warm_s.append(train.dispatch(bst, gbdt))
        if i < followed:
            step_scores.append(train.score_at(gbdt, idx_dev))
    log(f"warm-up dispatches {[round(s, 2) for s in warm_s]} s; compile so far "
        f"{meter.mark()}")
    if int(gbdt.iter_) <= first_sampled:
        raise RuntimeError(f"the booster is at iteration {gbdt.iter_}: no sampled "
                           f"tree was grown before the window")
    trace = train.traced_dispatch(ctx, bst, gbdt) if ctx["trace"] else None
    setup = meter.mark()
    setup_s = time.time() - ctx["t0"]

    # ---------------------------------------------------------------- window
    durations = []
    t_start = time.perf_counter()
    while True:
        durations.append(train.dispatch(bst, gbdt))
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
    score_before = np.asarray(jax.device_get(gbdt.score[0]))[:rows]
    steady_s = train.dispatch(bst, gbdt)
    # the steady tree's sample, as the step returned it: 0/1 a row
    included = np.asarray(jax.device_get(gbdt.bag_mask))[:rows] > 0
    LAST_RUN["included"] = included
    step_scores.append(train.score_at(gbdt, idx_dev))
    final_score = step_scores[-1]
    bst._ensure_finalized()
    trees = [train.tree_dict(t) for t in bst.trees]
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
    ref = refer(cfg, traffic, X, y, valued, score_before, sample, ctx["seed"],
                included=included)
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
    nums = numbers(program, ref, y_sample)
    limits = compare.load_limits(ctx["here"], ctx["cell"]["name"])
    correct, compared = compare.judge(nums, limits)
    leaves = [t["num_leaves"] for t in trees]
    log(f"reference: valued {followed} followed trees and the steady one (tree "
        f"{len(trees)}, sampled, {steady_s:.2f}s) over {rows} rows, scanned "
        f"{sum(s[2] for s in scans)} nodes, walked {len(trees)} trees on {len(sample)} "
        f"rows in {time.perf_counter() - t_ref:.1f}s; leaves per tree {leaves}; "
        f"sample {ref['sample']}")
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
        "work": {"rows": rows, "rows_sampled": top_k + other_k, "features": features,
                 "root_floor_s": floor_s, "root_floor_bound": bound_by},
        "trace": trace,
        # kept in memory for benchmarks/tests/readings_goss.py; never printed
        "state": {"X": X, "y": y, "sample": sample, "trees": trees, "ref": ref,
                  "program": program, "score_before": score_before,
                  "included": included},
        "info": {"numbers": nums, "leaves": leaves, "runner_up_feature_loss": runner_up,
                 "runner_up_node_loss": min(s[1] for s in scans), "steady_s": steady_s,
                 "warmup_s": warm_s, "dispatch_s": durations, "window_s": elapsed,
                 "sample": ref["sample"], "trees": len(trees),
                 "setup_parts_s": {"data": t_gen, "dataset_and_ingest": t_built - t_gen,
                                   "ingest": ingest.get("seconds"),
                                   "warmup": float(sum(warm_s)),
                                   "compile": setup["compile_s"]},
                 "compile": setup, "ingest": ingest, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
    }


def numbers(program: dict, ref: dict, y_sample) -> dict:
    """``compare.numbers`` and the two numbers of the sample."""
    nums = compare.numbers(program, ref, y_sample)
    nums["top_missed"] = float(ref["sample"]["top_missed"])
    nums["other_count_gap"] = float(ref["sample"]["other_count_gap"])
    return nums
