"""The ``train`` job for a data-parallel cell: one booster over the host's
chips (``tree_learner=data``, ``num_machines`` = the cell's chips), one host
process feeding every shard, driven through ``Booster.update`` exactly as
``jobs/train.py`` drives one chip. The set-up, the window, the steady tree,
the reference and the comparison are ``train``'s, step for step, through its
own functions; this job differs from it in five things.

1. **A probe that fails at once** on a program that keeps a float64 copy of
   float32 rows. Four workers' shares are 44,040,192 x 67 float32 = 11.8 GB;
   a program that widens them holds a 23.6 GB copy and checks it for the
   round trip through some 74 GB of temporaries (25 B a value) and about
   150 s of one host's time: the shares do not fit a run (PR 29 was stopped
   at 360 s). So before the table is made, ``lgb.Dataset`` is constructed on
   one 65,536-row float32 block; if it holds anything but the float32 rows,
   or timed a copy or a round-trip check, the run ends non-zero, in a
   second. Its seconds are ``info.setup_parts_s.probe``.
2. **The floor is per chip.** ``work.root_floor_s`` is the root pass of
   rows / chips against ONE chip's peaks (= all rows against all the chips'),
   so ``step.mfu_floor``, ``kernels.hist_roofline`` and
   ``kernels.hist_exec_roofline`` read what they read on one chip. ``work.rows``
   is a shard's rows too: the program's ``grow.*`` counts are the
   pace-setting shard's (the per-wave maximum over the devices), and the
   readers divide them by it. ``train_rate`` is the rows of the WHOLE table
   x dispatches / seconds.
3. **The trace is reduced per device** (``lib/xplane.reduce_device`` per
   plane, ``trace.per_device``) beside ``reduce_events``' mean.
4. **The host table is kept** for the reference instead of being made again
   (the program holds no copy of its own, and the four-chip host has the
   memory), and it is made by as many threads as the host has cores
   (``datagen.generate`` with its thread count raised for the call).
5. ``memory_peak`` and ``refer`` are forwarded, because ``rehearse.py`` and
   ``tests/readings.py`` reach for them on whatever job a cell names.
"""
import gc
import glob
import importlib.util
import os
import shutil
import time

import numpy as np

from lib import compare, datagen, peaks, program_counters, reference, work, xplane
from lib.compile_meter import CompileMeter

PROBE_ROWS = 65536
WIDENING_GAUGES = ("setup.dataset_to_float_s", "setup.dataset_lossless_check_s")


def _load_train():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    spec = importlib.util.spec_from_file_location("job_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


train = _load_train()
refer = train.refer
# rehearse.py and the tests put their own in its place on the CPU, which
# keeps no peak_bytes_in_use
memory_peak = train.memory_peak


def probe(ctx: dict) -> None:
    """Exit non-zero if ``lgb.Dataset`` widens one float32 block."""
    import lightgbm_tpu as lgb
    cfg = ctx["config"]
    rows = min(int(cfg["data"]["rows"]), PROBE_ROWS)
    X, y = datagen.generate(cfg["data"], rows, ctx["seed"])
    ds = lgb.Dataset(X, label=y, params=dict(cfg["params"]))
    ds.construct()
    held = np.asarray(ds.raw_data).dtype
    timed = [g for g in WIDENING_GAUGES if program_counters.gauge(g) is not None]
    ctx["log"](f"probe: lgb.Dataset on {rows} float32 rows holds {held}; timed {timed}")
    if held != np.float32 or timed:
        total = int(cfg["data"]["rows"]) * X.shape[1]
        raise SystemExit(
            f"benchmarks/jobs/train_dp.py: the program keeps a {held} copy of "
            f"float32 rows (it timed {timed or 'no copy'}). At this cell's "
            f"{int(cfg['data']['rows'])} x {X.shape[1]} that is a "
            f"{total * 8 / 1e9:.1f} GB copy and a round-trip check through "
            f"{total * 25 / 1e9:.0f} GB of temporaries on one host, some 150 s: "
            f"four workers' shares do not fit a run. Refused before the table "
            f"is built.")


def generate(data: dict, rows: int, seed: int):
    """``datagen.generate`` with as many threads as the host has cores (the
    generator's own 8 take 69 s for four workers' shares, 30 take 12): the
    same blocks from the same seeds, to the bit."""
    threads = datagen.THREADS
    datagen.THREADS = max(threads, min(32, os.cpu_count() or 1))
    try:
        return datagen.generate(data, rows, seed)
    finally:
        datagen.THREADS = threads


def traced_dispatch(ctx, bst, gbdt) -> dict:
    """``train.traced_dispatch`` with every device's own reduction kept."""
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
    shutil.rmtree(out, ignore_errors=True)
    reduced = reduce_per_device(recorded)
    ctx["log"](f"trace: {size / 2**20:.1f} MiB xplane, {reduced.get('n_events')} device "
               f"events a plane, {reduced.get('devices')} planes, reduced in "
               f"{time.perf_counter() - t:.1f}s; busy {reduced.get('busy_s')} of "
               f"{reduced.get('window_s')} s; classes {reduced.get('class_s')}")
    for plane, d in reduced.get("per_device", {}).items():
        ctx["log"](f"trace plane {plane}: busy {d['busy_s']:.4f}s classes {d['class_s']}")
    for name, seconds in reduced.get("ops", [])[:30]:
        ctx["log"](f"trace op {seconds:10.4f}s  {name}")
    for name, seconds in reduced.get("gaps", [])[:10]:
        ctx["log"](f"trace gap {seconds:10.4f}s  {name}")
    if not reduced.get("devices"):
        raise RuntimeError("the trace holds no device operation")
    return reduced


def reduce_per_device(recorded: dict, annotation: str = "bench.traced") -> dict:
    """``xplane.reduce_events``' mean over the devices, and under
    ``per_device`` each plane's own numbers in the same window."""
    reduced = xplane.reduce_events(recorded, annotation)
    host = recorded.get("host", [])
    spans = [(s, s + d) for n, s, d in host if n == annotation]
    per_device = {}
    for plane, events in sorted(recorded["devices"].items()):
        if not events:
            continue
        window = ((min(s for s, _ in spans), max(e for _, e in spans)) if spans else
                  (min(e[2] for e in events), max(e[2] + e[3] for e in events)))
        per_device[plane] = xplane.reduce_device(events, window, host)
    return dict(reduced, per_device=per_device)


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    rows = int(cfg["data"]["rows"])
    chips = int(cfg["params"]["num_machines"])
    features = datagen.num_features(cfg["data"])
    followed = int(traffic["followed_trees"])
    warmup = int(traffic["warmup_dispatches"])
    if warmup < followed:
        raise ValueError("the reference follows set-up's dispatches: "
                         "warmup_dispatches must be >= followed_trees")
    # the floor of ONE chip on ITS rows: a shard's root pass at a chip's peak
    floor_s, bound_by = work.root_pass_floor_s(
        rows // chips, features, int(cfg["params"]["max_bin"]),
        peaks.peaks_for(ctx["device"]["kind"]))
    import lightgbm_tpu as lgb        # the package's import is set-up's, not the probe's
    t_probe = time.time()
    probe(ctx)
    probe_s = time.time() - t_probe
    meter = CompileMeter()

    # ---------------------------------------------------------------- set-up
    t_gen = time.time()
    X, y = generate(cfg["data"], rows, ctx["seed"])
    t_gen = time.time() - t_gen
    log(f"data: {rows} x {features} float32 from seed {ctx['seed']} in {t_gen:.1f}s, "
        f"{float(y.mean()):.4f} positive")
    sample = np.sort(np.random.default_rng([ctx["seed"], 1]).choice(
        rows, size=min(int(traffic["sample_rows"]), rows), replace=False))
    X_sample, y_sample = X[sample], y[sample]      # fancy indexing copies
    t_build = time.time()
    # the program reads the float32 rows where they lie and keeps no copy:
    # X stays for the reference
    train_set = lgb.Dataset(X, label=y)
    bst, gbdt = train.build(ctx, train_set)
    del train_set
    t_build = time.time() - t_build
    ingest = dict(gbdt._ingest_report or {})
    log(f"booster: residency={gbdt.residency} learner={gbdt.pctx.strategy} over "
        f"{gbdt.pctx.num_devices} devices, kernel={gbdt.spec.hist_kernel} "
        f"slots={gbdt.spec.hist_slots} chunk={gbdt.spec.chunk_rows}; ingest {ingest}")
    if gbdt.residency != cfg["expect"]["residency"]:
        raise RuntimeError(f"residency {gbdt.residency!r}, the cell is sized for "
                           f"{cfg['expect']['residency']!r}")
    if gbdt.pctx.strategy != cfg["params"]["tree_learner"] or gbdt.pctx.num_devices != chips:
        raise RuntimeError(f"the booster runs {gbdt.pctx.strategy!r} over "
                           f"{gbdt.pctx.num_devices} device(s); the cell is "
                           f"{cfg['params']['tree_learner']!r} over {chips}")
    idx_dev = jnp.asarray(sample.astype(np.int32))
    warm_s, step_scores = [], []
    for i in range(warmup):
        warm_s.append(train.dispatch(bst, gbdt))
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
        durations.append(train.dispatch(bst, gbdt))
        elapsed = time.perf_counter() - t_start
        if elapsed >= ctx["seconds"]:
            break
    inside = meter.since(setup)
    if any(inside.values()):
        raise RuntimeError(f"a program compiled or loaded inside the window: {inside}")
    peak = memory_peak()
    peaks_each = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in gbdt.pctx.devices]
    trees_done = len(durations)
    rate = rows * trees_done / elapsed / 1e6
    log(f"window: {trees_done} dispatches in {elapsed:.3f}s = {rate:.4f} Mrow-tree/s; "
        f"dispatch seconds {[round(d, 3) for d in durations]}; HBM peak "
        f"{peak / 2**30:.3f} GiB, by device {[round(p / 2**30, 3) for p in peaks_each]}")

    # ------------------------------------------- what the timed path produced
    t_after = time.perf_counter()
    score_before = np.asarray(jax.device_get(gbdt.score[0]))[:rows]
    steady_s = train.dispatch(bst, gbdt)
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
    after_s = time.perf_counter() - t_after
    log(f"reference: valued {followed} followed trees and the steady one (tree "
        f"{len(trees)}, {steady_s:.2f}s) over {rows} rows, scanned "
        f"{sum(s[2] for s in scans)} nodes, walked {len(trees)} trees on {len(sample)} "
        f"rows in {time.perf_counter() - t_ref:.1f}s ({after_s:.1f}s since the window); "
        f"leaves per tree {leaves}")
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
        # a shard's rows and a chip's floor (the docstring's second point)
        "work": {"rows": rows // chips, "rows_table": rows, "chips": chips,
                 "features": features, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
        "trace": trace,
        # kept in memory for benchmarks/tests/readings.py; never printed
        "state": {"X": X, "y": y, "sample": sample, "trees": trees, "ref": ref,
                  "program": program, "score_before": score_before},
        "info": {"numbers": nums, "leaves": leaves, "runner_up_feature_loss": runner_up,
                 "runner_up_node_loss": min(s[1] for s in scans), "steady_s": steady_s,
                 "warmup_s": warm_s, "dispatch_s": durations, "window_s": elapsed,
                 "setup_parts_s": {"probe": probe_s, "data": t_gen,
                                   "dataset_and_ingest": t_build,
                                   "ingest": ingest.get("seconds"),
                                   "ingest_by_device": ingest.get("device_seconds"),
                                   "warmup": float(sum(warm_s)),
                                   "compile": setup["compile_s"]},
                 "after_window_s": after_s,
                 "memory_peak_bytes_by_device": peaks_each,
                 "trace_by_device": None if trace is None else {
                     plane: {"busy_s": d["busy_s"], "class_s": d["class_s"]}
                     for plane, d in trace["per_device"].items()},
                 "compile": setup, "ingest": ingest, "root_floor_s": floor_s,
                 "root_floor_bound": bound_by},
    }
