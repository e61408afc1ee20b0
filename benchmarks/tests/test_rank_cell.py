"""``istella220-255-train`` on the CPU at a tiny size: the job
(``jobs/train_rank.py``), its two readers, the scope reduction, and the
control and every planted fault read ``correct`` false by the cell's own
limits; a program that would bake the query layout into its step is refused
before the first dispatch.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_rank_cell.py -q
"""
import os

import numpy as np
import pytest

import run as harness
from lib import compare, datagen_rank, program_counters, xplane_scope
from test_correct import altered, half, late, stale

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "istella220-255-train"
ROWS = 24000
SHARED = 27       # the per-layer metrics the cell reports beside its own two


def tiny_ctx() -> dict:
    ctx = harness.resolve_cell(CELL)
    data = ctx["config"]["data"]
    data.update(rows=ROWS, block_rows=4096)
    data["queries"].update(count=ROWS // 40, mean=40.0, max=64)
    ctx["config"]["params"].update(num_leaves=31, device="cpu", verbose=-1,
                                   min_sum_hessian_in_leaf=1.0)
    ctx["traffic"]["sample_queries"] = 64
    return harness.make_ctx(ctx, seed=2147483659, seconds=0.0, trace=False,
                            device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
                            log=lambda msg: None)


def rank_job():
    job = harness.load_module(os.path.join(HERE, "jobs", "train_rank.py"),
                              "job_train_rank_test")
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    return job


def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


@pytest.fixture(scope="module")
def tiny_run():
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    ctx = tiny_ctx()
    job = rank_job()
    run = job.run(ctx)
    yield job, ctx, run
    obs.reset_for_tests()


def test_the_cell_declares_its_files_and_its_metrics():
    ctx = harness.resolve_cell(CELL)
    declared = {m["name"]: m for m in ctx["per_layer"]}
    assert len(declared) == SHARED + 2
    for name in declared:
        assert callable(reader(name).read), name
    for name in ("objective.grad_share", "objective.pair_useful_share"):
        assert (declared[name]["layer"], declared[name]["moves"],
                declared[name]["workloads"]) == ("objective", "train_rate", [CELL])
    assert ctx["cell"]["chips"] == 1 and ctx["traffic"]["job"] == "train_rank"
    assert ctx["config"]["reduced"] == [] and ctx["config"]["params"]["objective"] == "lambdarank"
    limits = compare.load_limits(HERE, CELL)
    assert set(limits) == {"count_mismatch", "leaf_gap_max", "pred_gap", "score_gap",
                           "grad_gap"}


def test_the_table_holds_its_queries_and_grades():
    data = harness.resolve_cell(CELL)["config"]["data"]
    sizes = datagen_rank.query_sizes(data["queries"], int(data["rows"]), data["base_seed"])
    assert len(sizes) == 23219 and sizes.sum() == 7325625
    assert sizes.min() >= 1 and sizes.max() == 4096
    small = dict(data, rows=20000, block_rows=4096,
                 queries=dict(data["queries"], count=200, max=512))
    Xa, la, sa = datagen_rank.generate(small, 20000, 1)
    Xb, lb, sb = datagen_rank.generate(small, 20000, 2147483659)
    assert np.array_equal(la, lb) and np.array_equal(sa, sb)       # seeds order columns only
    assert np.bincount(la.astype(np.int64)).tolist() == [16000, 2000, 1200, 600, 200]


def test_a_sound_run_is_correct_and_reads_its_metrics(tiny_run):
    job, ctx, run = tiny_run
    assert run["correct"], (run["compared"], run["info"]["numbers"])
    assert run["attempted"] >= 1 and run["failed"] == 0
    pairs = program_counters.counter("rank.pairs")
    slots = program_counters.counter("rank.pair_slots")
    st = run["state"]
    qb = np.concatenate([[0], np.cumsum(st["sizes"])])
    lab = st["label"]
    assert pairs == sum(int(np.sum(lab[lo:hi, None] > lab[None, lo:hi]))
                        for lo, hi in zip(qb[:-1], qb[1:]))
    assert reader("objective.pair_useful_share").read(run) == pytest.approx(100.0 * pairs / slots)
    assert reader("objective.grad_share").read(run) is None        # untraced
    traced = dict(run, trace={"busy_s": 2.0, "scopes": {"seconds": {"step.gradients": 0.5}}})
    assert reader("objective.grad_share").read(traced) == pytest.approx(25.0)


@pytest.mark.parametrize("fault", [stale, half, altered, late(stale), late(half),
                                   late(altered)], ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    job = rank_job()
    job.dispatch = fault(job)
    run = job.run(tiny_ctx())
    assert not run["correct"], (fault.__name__, run["info"]["numbers"])


def test_the_control_and_the_faults_of_the_readings_are_not_correct(tiny_run):
    from readings_rank import readings
    job, ctx, run = tiny_run
    out = readings(job, ctx, run, compare.load_limits(HERE, CELL))
    for name in ("control_bf16", "fault_half", "fault_stale"):
        assert not out[name]["correct"], (name, out[name])
    assert "grad_gap" in out["control_bf16"]["over"]


def test_a_program_that_bakes_the_query_layout_in_is_refused(monkeypatch):
    from lightgbm_tpu.boosting.gbdt import GBDT
    plain = GBDT._step_consts

    def without(self):
        consts, valid = plain(self)
        consts.pop("objective")
        return consts, valid

    monkeypatch.setattr(GBDT, "_step_consts", without)
    with pytest.raises(SystemExit) as e:
        rank_job().run(tiny_ctx())
    assert "cannot run this cell" in str(e.value)


def test_scope_seconds_from_a_recorded_plane():
    """Self seconds by scope path on a plane written with the proto module:
    a ``while`` under ``step.gradients`` holding a sort under
    ``rank.pairwise``, and an operation outside both."""
    pb2 = xplane_scope._pb2()
    if pb2 is None:
        pytest.skip("no xplane proto module installed")
    space = pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].id, plane.stat_metadata[1].name = 1, "tf_op"
    ops = [("%while.1 = (s32[]) while(x)", "jit(step)/step.gradients/while", 0, 1000),
           ("%sort.2 = f32[8] sort(y)", "jit(step)/step.gradients/rank.pairwise/sort", 100, 600),
           ("%fusion.3 = f32[8] fusion(z), kind=kLoop", "jit(step)/step.grow/wave.route", 1200, 300)]
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (text, path, start, dur) in enumerate(ops, start=1):
        md = plane.event_metadata[i]
        md.id, md.name = i, text
        md.stats.add(metadata_id=1, str_value=path)
        line.events.add(metadata_id=i, offset_ps=start * 1000, duration_ps=dur * 1000)
    path = os.path.join(HERE, ".trace_test.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    try:
        events = xplane_scope.scoped_events(path)["/device:TPU:0"]
        got = xplane_scope.scope_seconds(events, (0, 10 ** 6),
                                         ("step.gradients", "rank.pairwise", "step.grow"))
    finally:
        os.remove(path)
    assert got["seconds"]["step.gradients"] == pytest.approx((400 + 600) / 1e9)
    assert got["seconds"]["rank.pairwise"] == pytest.approx(600 / 1e9)
    assert got["seconds"]["step.grow"] == pytest.approx(300 / 1e9)
    assert got["ops"]["rank.pairwise"][0][0] == "sort:sort"
