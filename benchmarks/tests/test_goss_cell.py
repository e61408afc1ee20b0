"""``criteo67-255-goss-train`` on the CPU at a tiny size: the job
(``jobs/train_goss.py``), its floor, the reader ``sampling.rows_in_share``,
and the control and every planted fault of ``readings_goss.py`` read
``correct`` false by the cell's own limits.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_goss_cell.py -q
"""
import os

import pytest

import run as harness
from lib import compare, peaks, program_counters, reference_goss, work
from test_correct import ROWS, tiny_ctx

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "criteo67-255-goss-train"
SHARED = 16       # the per-layer metrics every cell reports


def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


def goss_job():
    job = harness.load_module(os.path.join(HERE, "jobs", "train_goss.py"),
                              "job_train_goss_test")
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    return job


@pytest.fixture(scope="module")
def tiny_run():
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    ctx = tiny_ctx(CELL)
    job = goss_job()
    run = job.run(ctx)
    yield job, ctx, run
    obs.reset_for_tests()


def test_the_cell_declares_its_files_and_its_metric():
    ctx = harness.resolve_cell(CELL)
    declared = {m["name"]: m for m in ctx["per_layer"]}
    assert len(declared) == SHARED + 1
    for name in declared:
        assert callable(reader(name).read), name
    own = declared["sampling.rows_in_share"]
    assert (own["layer"], own["moves"], own["workloads"]) == (
        "sampling", "train_rate", [CELL])
    assert [m["name"] for m in ctx["end_to_end"]] == [
        "train_rate", "hbm_peak_gib", "setup_s"]
    assert ctx["cell"]["chips"] == 1 and ctx["traffic"]["job"] == "train_goss"
    assert (ctx["traffic"]["warmup_dispatches"], ctx["traffic"]["followed_trees"]) == (12, 3)
    # criteo67-255's table, semantics and parameters, letter for letter
    twin = harness.resolve_cell("criteo67-255-train")["config"]
    cfg = ctx["config"]
    assert cfg["data"] == twin["data"] and cfg["semantics"] == twin["semantics"]
    assert cfg["reduced"] == ["rows"] == twin["reduced"]
    params = dict(cfg["params"])
    assert (params.pop("boosting"), params.pop("top_rate"), params.pop("other_rate")) == (
        "goss", 0.2, 0.1)
    assert params == twin["params"]
    assert set(compare.load_limits(HERE, CELL)) >= {"top_missed", "other_count_gap",
                                                    "leaf_gap_max", "score_gap"}


def test_a_sound_run_is_correct_and_its_steady_tree_is_sampled(tiny_run):
    _job, ctx, run = tiny_run
    assert run["correct"], run["compared"]
    s = run["info"]["sample"]
    top_k, other_k, amplify = reference_goss.counts(ROWS, 0.2, 0.1)
    assert (s["top_k"], s["other_k"], s["amplify"]) == (top_k, other_k, amplify)
    assert s["top_missed"] == 0 and s["other_count_gap"] < 1
    assert abs(s["rows_top"] - top_k) <= s["band_rows"]
    # most of the rows that carry the score comparison are out-of-sample rows
    assert 0.6 < s["sample_share_out"] < 0.8
    assert run["info"]["trees"] == 12 + run["attempted"] + 1
    assert {"top_missed", "other_count_gap"} <= set(run["compared"])


def test_the_floor_is_the_samples_root_pass(tiny_run):
    _job, ctx, run = tiny_run
    top_k, other_k, _ = reference_goss.counts(ROWS, 0.2, 0.1)
    floor_s, bound = work.root_pass_floor_s(top_k + other_k, 67, 255,
                                            peaks.peaks_for("TPU v5 lite"))
    w = run["work"]
    assert (w["rows"], w["rows_sampled"]) == (ROWS, top_k + other_k)
    assert (w["root_floor_s"], w["root_floor_bound"]) == (floor_s, bound)
    whole, _ = work.root_pass_floor_s(ROWS, 67, 255, peaks.peaks_for("TPU v5 lite"))
    assert w["root_floor_s"] == pytest.approx(0.3 * whole, rel=1e-3)


def test_readers_read_the_sample_and_the_passes_over_it(tiny_run):
    _job, _ctx, run = tiny_run
    rows_in = program_counters.per_tree("sample.rows_in")
    touched = program_counters.per_tree("grow.hist_rows_touched")
    assert len(rows_in) == len(touched) == run["info"]["trees"]
    assert rows_in[:10] == [float(ROWS)] * 10 and max(rows_in[10:]) < 0.32 * ROWS
    share = reader("sampling.rows_in_share")
    # untraced: the mean over the run's trees; traced: the traced tree, which
    # follows the twelve warm-ups and is a sampled one
    assert share.read(run) == pytest.approx(100.0 * sum(rows_in) / len(rows_in) / ROWS)
    traced = dict(run, trace={"class_s": {"matmul": 1.0, "custom": 0.0}})
    assert share.read(traced) == pytest.approx(100.0 * rows_in[12] / ROWS)
    assert 29.0 < share.read(traced) < 31.0
    passes = reader("kernels.hist_passes_per_tree").read(traced)
    useful = reader("kernels.hist_useful_share").read(traced)
    # (at this size a chunk is half of the table: passes cannot show the
    # sample; tests/test_row_sampling.py holds them at 256-row chunks)
    assert passes == touched[12] / ROWS and 0 < useful <= 100.0


def test_a_program_without_the_count_reads_as_nothing(tiny_run):
    from lightgbm_tpu import observability as obs
    _job, _ctx, run = tiny_run
    saved = obs.get_registry()
    snapshot = (dict(saved._counters), dict(saved._gauges), dict(saved._summaries))
    saved.reset()
    try:
        assert reader("sampling.rows_in_share").read(run) is None
    finally:
        saved._counters, saved._gauges, saved._summaries = (
            dict(snapshot[0]), dict(snapshot[1]), dict(snapshot[2]))


def test_the_control_and_every_planted_fault_read_not_correct(tiny_run):
    from readings_goss import SAMPLE_FAULTS, VALUE_FAULTS, controls
    job, ctx, run = tiny_run
    limits = compare.load_limits(HERE, CELL)
    read = controls(job, ctx["config"], ctx["traffic"], run, ctx["seed"], limits)
    assert set(read) == {n for n, _ in VALUE_FAULTS + SAMPLE_FAULTS}
    for name, numbers in read.items():
        assert not numbers["correct"] and numbers["over"], (name, numbers)
    assert "leaf_gap_max" in read["fault_no_amplify"]["over"]
    assert "score_gap" in read["fault_unscored"]["over"]
    assert "top_missed" in read["fault_half_sample"]["over"]
    assert "other_count_gap" in read["fault_half_sample"]["over"]


def test_the_window_never_opens_on_unsampled_trees():
    ctx = tiny_ctx(CELL)
    ctx["traffic"]["warmup_dispatches"] = 10
    with pytest.raises(ValueError, match="unsampled trees"):
        goss_job().run(ctx)
