"""The ``train`` job for a learning-to-rank cell (``objective=lambdarank``):
one booster over a query-grouped table (``lgb.Dataset(X, label, group=...)``)
driven through ``Booster.update`` exactly as ``jobs/train.py`` drives it,
through ``train``'s own functions: ``warmup_dispatches`` set-up dispatches
(the first ``followed_trees`` of them valued by the reference), the traced
dispatch, the window, then one steady tree. It differs in five things.

1. **The table** is ``lib/datagen_rank.py``'s: ``datagen``'s columns, graded
   labels and contiguous queries, all from the configuration's base seed.
2. **A program that cannot run the cell ends the run** right after the
   booster is built, before the first dispatch: one whose objective keeps no
   device arrays for the step to take as an ARGUMENT
   (``GBDT._step_consts()["objective"]``) and publishes no ``rank.queries``.
   Such a program bakes the query layout (at 7.3M docs, 86 MB of index, mask
   and row map) into its executable as constants.
3. **The reference is lib/reference_rank.py**: the followed trees are valued
   from the initial score 0 (tree 1: every score tied) and the steady tree
   from the program's score before it, with the reference's own gradients
   computed on the chip for every doc (form b) and summed per leaf in
   float64; on the ``sample_queries`` queries drawn from ``--seed``, the
   program's own grad/hess (its objective called on the step's score, at
   tree 1's score 0 and at the steady tree's score) against float64 (form a):
   ``grad_gap``.
4. **The trace is also reduced by scope** (``lib/xplane_scope.py``): the
   self seconds under ``step.gradients`` and ``rank.pairwise`` and their
   top operations, under ``trace.scopes``.
5. ``memory_peak``, ``dispatch`` and ``refer`` are module names, so that
   ``tests/test_rank_cell.py`` and ``tests/readings_rank.py`` can put their
   own in their place; ``refer`` values the trees with the reference in the
   precision asked ("f32", or "bf16": the control), from a gradient of its
   own where one is given (a planted fault).
"""
import glob
import importlib.util
import os
import shutil
import time

import numpy as np

from lib import compare, datagen, datagen_rank, peaks, reference, reference_rank, work
from lib import xplane, xplane_scope
from lib.compile_meter import CompileMeter

SCOPES = ("step.gradients", "rank.pairwise")


def _load_train():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    spec = importlib.util.spec_from_file_location("job_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


train = _load_train()
# the tests put their own in its place on the CPU, which keeps no
# peak_bytes_in_use, and plant their faults in ``dispatch``
# (benchmarks/tests/test_rank_cell.py)
memory_peak = train.memory_peak
dispatch = train.dispatch


def semantics(cfg: dict) -> dict:
    sem = cfg["semantics"]
    return {"gains": np.asarray(sem["label_gain"], np.float64),
            "sigma": float(sem["sigmoid"]), "max_position": int(sem["max_position"])}


def refer(cfg: dict, sizes, label, X, valued, score_before, sample_rows, precision="f32",
          gradients=None):
    """The followed trees from score 0, the steady tree (the last) from
    ``score_before``: leaf values, counts, gains and sample scores."""
    sem = cfg["semantics"]
    grads = gradients or reference_rank.DeviceGradients(sizes, label, precision=precision,
                                                        **semantics(cfg))
    return reference_rank.value_trees(
        X, valued, grads, learning_rate=float(sem["learning_rate"]),
        lambda_l2=float(sem["lambda_l2"]), init_score=float(sem["init_score"]),
        sample=sample_rows, restart={len(valued) - 1: score_before})


def program_gradients(gbdt):
    """The program's own grad/hess as the step computes them, as a function
    of a score vector: its objective, with its device arrays handed in as
    the step hands them in."""
    import jax
    import jax.numpy as jnp
    obj = gbdt.objective

    def grads(arrays, score, label):
        saved, obj.device_arrays = obj.device_arrays, arrays
        try:
            return obj.gradients(score, label, None)
        finally:
            obj.device_arrays = saved

    fn = jax.jit(grads)

    def at(score_dev, rows):
        g, h = fn(obj.device_arrays, score_dev, gbdt.label)
        idx = jnp.asarray(rows.astype(np.int32))
        return (np.asarray(jax.device_get(g[0][idx]), np.float64),
                np.asarray(jax.device_get(h[0][idx]), np.float64))

    return at


def runs_this_cell(gbdt) -> str:
    """'' where the program takes the objective's arrays as a step argument
    and counts its pairs, else why not."""
    from lib import program_counters
    consts = gbdt._step_consts()[0]
    if consts.get("objective") is None:
        return ("its training step does not take the objective's device arrays "
                "as an argument: the query layout would be baked into the executable")
    if program_counters.gauge("rank.queries") is None:
        return "its objective publishes no rank.queries"
    return ""


def traced_dispatch(ctx, bst, gbdt) -> dict:
    """``train.traced_dispatch``, with the xplane also reduced by scope
    before it is deleted."""
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
    t = time.perf_counter()
    recorded = xplane.device_events(path)
    reduced = xplane.reduce_events(recorded)
    reduced["scopes"] = xplane_scope.reduce_scopes(path, recorded, SCOPES)
    log = ctx["log"]
    log(f"trace: {os.path.getsize(path) / 2**20:.1f} MiB xplane, {reduced.get('n_events')} "
        f"device events, reduced in {time.perf_counter() - t:.1f}s; busy "
        f"{reduced.get('busy_s')} of {reduced.get('window_s')} s; classes "
        f"{reduced.get('class_s')}")
    for name, seconds in reduced.get("ops", [])[:25]:
        log(f"trace op {seconds:10.4f}s  {name}")
    for name, seconds in reduced.get("gaps", [])[:10]:
        log(f"trace gap {seconds:10.4f}s  {name}")
    scopes = reduced["scopes"]
    if scopes:
        for s in SCOPES:
            log(f"trace scope {s}: {scopes['seconds'][s]:.4f}s; ops {scopes['ops'][s]}")
        for path, seconds in scopes["paths"]:
            log(f"trace scope path {seconds:10.4f}s  {path}")
    else:
        log("trace scope: the trace names no scope (no tf_op, or no xplane proto module)")
    shutil.rmtree(out, ignore_errors=True)
    if not reduced.get("devices"):
        raise RuntimeError("the trace holds no device operation")
    return reduced


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    params = cfg["params"]
    rows = int(cfg["data"]["rows"])
    features = datagen.num_features(cfg["data"])
    followed = int(traffic["followed_trees"])
    warmup = int(traffic["warmup_dispatches"])
    if params.get("objective") != "lambdarank":
        raise ValueError("jobs/train_rank.py drives an objective=lambdarank configuration")
    if warmup < followed:
        raise ValueError("the reference follows set-up's dispatches: "
                         "warmup_dispatches must be >= followed_trees")
    floor_s, bound_by = work.root_pass_floor_s(
        rows, features, int(params["max_bin"]), peaks.peaks_for(ctx["device"]["kind"]))
    meter = CompileMeter()

    # ---------------------------------------------------------------- set-up
    X, label, sizes = datagen_rank.generate(cfg["data"], rows, ctx["seed"])
    t_gen = time.time() - ctx["t0"]
    queries = reference_rank.sample_queries(sizes, ctx["seed"], int(traffic["sample_queries"]),
                                            int(traffic["per_class"]))
    sample = reference_rank.sample_rows(sizes, queries)
    log(f"data: {rows} x {features} float32 in {len(sizes)} queries ({int(sizes.min())}-"
        f"{int(sizes.max())} docs) from seed {ctx['seed']}, grades "
        f"{np.bincount(label.astype(np.int64)).tolist()}; sample {len(queries)} queries, "
        f"{len(sample)} docs")
    X_sample, label_sample = X[sample], label[sample]       # fancy indexing copies
    # the float32 rows are kept: lgb.Dataset holds them as they are (no
    # copy), and the reference reads them again after the window, where
    # making them anew from the seed would cost the run half a minute
    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=label, group=sizes)
    bst, gbdt = train.build(ctx, train_set)
    del train_set
    t_built = time.time() - ctx["t0"]
    missing = runs_this_cell(gbdt)
    if missing:
        raise SystemExit(f"benchmarks/jobs/train_rank.py: the program cannot run this "
                         f"cell: {missing}. Refused before the first dispatch.")
    ingest = dict(gbdt._ingest_report or {})
    obj = gbdt.objective
    log(f"booster: residency={gbdt.residency} kernel={gbdt.spec.hist_kernel} "
        f"slots={gbdt.spec.hist_slots} chunk={gbdt.spec.chunk_rows}; classes "
        f"{[(c['m'], c['queries']) for c in obj.classes]}; pairs {obj.pairs} of "
        f"{obj.pair_slots} slots; ingest {ingest}")
    if gbdt.residency != cfg["expect"]["residency"]:
        raise RuntimeError(f"residency {gbdt.residency!r}, the cell is sized for "
                           f"{cfg['expect']['residency']!r}")
    idx_dev = jnp.asarray(sample.astype(np.int32))
    warm_s, step_scores = [], []
    for i in range(warmup):
        warm_s.append(dispatch(bst, gbdt))
        if i < followed:
            step_scores.append(train.score_at(gbdt, idx_dev))
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
    t_prog = time.perf_counter()
    grads_at = program_gradients(gbdt)
    prog_tied = grads_at(jnp.zeros_like(gbdt.score), sample)
    prog_steady = grads_at(gbdt.score, sample)
    score_before = np.asarray(jax.device_get(gbdt.score[0]))[:rows]
    prog_grads_s = time.perf_counter() - t_prog
    steady_s = dispatch(bst, gbdt)
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
    del bst, gbdt, idx_dev, obj

    # ------------------------------------------------------------- reference
    t_ref = time.perf_counter()
    if not np.array_equal(X[sample], X_sample):
        raise RuntimeError("the program changed the rows it was handed")
    sem = cfg["semantics"]
    valued = trees[:followed] + [trees[-1]]
    ref = refer(cfg, sizes, label, X, valued, score_before, sample)
    t_valued = time.perf_counter() - t_ref
    kw = semantics(cfg)
    ref_tied = reference_rank.sample_gradients(np.zeros(len(sample)), label_sample, sizes,
                                               queries, **kw)
    ref_steady = reference_rank.sample_gradients(score_before[sample], label_sample, sizes,
                                                 queries, **kw)
    t_sampled = time.perf_counter() - t_ref - t_valued
    program = {"valued": valued, "followed": followed, "step_scores": step_scores,
               "predict_followed": predict_followed, "final_score": final_score,
               "walk_all": reference.walk(X_sample, trees, float(sem["init_score"])),
               "init_score": float(sem["init_score"]),
               "grads": {"tied": prog_tied, "steady": prog_steady}}
    ref["grads"] = {"tied": ref_tied, "steady": ref_steady}
    nums = numbers(program, ref, sizes, queries)
    limits = compare.load_limits(ctx["here"], ctx["cell"]["name"])
    correct, compared = compare.judge(nums, limits)
    leaves = [t["num_leaves"] for t in trees]
    log(f"reference: valued {followed} followed trees and the steady one (tree "
        f"{len(trees)}, {steady_s:.2f}s) over {rows} rows in {t_valued:.1f}s, sampled "
        f"gradients of {len(queries)} queries in {t_sampled:.1f}s, walked {len(trees)} "
        f"trees on {len(sample)} rows; {time.perf_counter() - t_ref:.1f}s in all (the "
        f"program's gradients {prog_grads_s:.1f}s before it); leaves per tree {leaves}")
    log("all numbers: " + ", ".join(f"{k}={v:.3e}" for k, v in nums.items()))
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
        # kept in memory for benchmarks/tests/readings_rank.py; never printed
        "state": {"X": X, "label": label, "sizes": sizes, "queries": queries,
                  "sample": sample, "trees": trees, "ref": ref, "program": program,
                  "score_before": score_before},
        "info": {"numbers": nums, "leaves": leaves, "steady_s": steady_s,
                 "warmup_s": warm_s, "dispatch_s": durations, "window_s": elapsed,
                 "sample_queries": len(queries), "sample_rows": len(sample),
                 "setup_parts_s": {"data": t_gen, "dataset_and_ingest": t_built - t_gen,
                                   "ingest": ingest.get("seconds"),
                                   "warmup": float(sum(warm_s)),
                                   "compile": setup["compile_s"]},
                 "compile": setup, "ingest": ingest, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
    }


def numbers(program: dict, ref: dict, sizes, queries) -> dict:
    """The comparison's numbers (``lib/compare.py``'s names and meaning where
    they exist there): counts, leaf values and gains of the valued trees,
    the followed trees' predictions, the resident score, and the sampled
    queries' gradients (``grad_gap``: the worst of tree 1's tied scores and
    the steady tree's; ``hess_gap`` the same for the hessians, printed)."""
    k = int(program["followed"])
    mismatch = 0
    leaf_max = leaf_med = gain_max = gain_med = leaf_steady = 0.0
    for t, tree in enumerate(program["valued"]):
        L = int(tree["num_leaves"])
        mismatch += int(np.sum(np.asarray(tree["leaf_count"][:L]) != ref["leaf_count"][t]))
        mismatch += int(np.sum(np.asarray(tree["internal_count"][:L - 1])
                               != ref["node_count"][t]))
        leaf = compare.gaps(np.asarray(tree["leaf_value"][:L], np.float64),
                            ref["leaf_value"][t])
        gain = compare.gaps(np.asarray(tree["split_gain"][:L - 1], np.float64), ref["gain"][t])
        leaf_max, leaf_med = max(leaf_max, leaf[0]), max(leaf_med, leaf[1])
        gain_max, gain_med = max(gain_max, gain[0]), max(gain_med, gain[1])
        if t >= k:
            leaf_steady = max(leaf_steady, leaf[0])
    ref_last = ref["sample_score"][k - 1]
    scale = float(np.sqrt(np.mean((ref_last - program["init_score"]) ** 2)))
    pred_gap = float(np.max(np.abs(program["predict_followed"] - ref_last))) / scale
    score_gap = float(np.max(np.abs(program["final_score"] - program["walk_all"]))) / scale
    nums = {"count_mismatch": float(mismatch), "leaf_gap_max": leaf_max,
            "leaf_gap_med": leaf_med, "gain_gap_max": gain_max, "gain_gap_med": gain_med,
            "pred_gap": pred_gap, "score_gap": score_gap, "leaf_gap_steady": leaf_steady}
    for at in ("tied", "steady"):
        (g, h), (gr, hr) = program["grads"][at], ref["grads"][at]
        nums[f"grad_gap_{at}"] = reference_rank.grad_gap(g, gr, sizes, queries)
        nums[f"hess_gap_{at}"] = reference_rank.grad_gap(h, hr, sizes, queries)
    nums["grad_gap"] = max(nums["grad_gap_tied"], nums["grad_gap_steady"])
    nums["hess_gap"] = max(nums["hess_gap_tied"], nums["hess_gap_steady"])
    return nums
