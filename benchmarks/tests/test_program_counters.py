"""The readers of what the program counts about itself
(``lib/program_counters.py`` and the eight ``metrics/`` files that use it),
on a ``run`` from the ``train`` job at a tiny size on the CPU, as
``rehearse.py tiny`` makes it. Counts and shares only: a CPU run gives no
device number, and none is asserted. On an empty registry (a program that
publishes nothing) every reader reports nothing, never 0.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_program_counters.py -q
"""
import os

import pytest

import run as harness
from lib import program_counters
from test_correct import ROWS, job_module, tiny_ctx

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "criteo67-255-train"
NEW = ["grower.waves_per_tree", "grower.route_useful_share",
       "kernels.hist_passes_per_tree", "kernels.hist_useful_share",
       "kernels.hist_exec_roofline", "compile.step_traces",
       "compile.step_executables", "setup.dataset_s"]


def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


@pytest.fixture(scope="module")
def tiny_run():
    """The whole of a run on the CPU at a tiny size (test_correct's, which
    is rehearse.py's ``tiny`` with every row in the sample)."""
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    run = job_module().run(tiny_ctx(CELL))
    assert run["correct"]
    yield run
    obs.reset_for_tests()


def test_every_new_metric_is_declared_for_the_cell_with_a_reader():
    ctx = harness.resolve_cell(CELL)
    declared = {m["name"]: m for m in ctx["per_layer"]}
    for name in NEW:
        assert name in declared, name
        assert callable(reader(name).read)
        assert declared[name]["source"] in ("program_span", "device_trace")


def test_readers_read_the_programs_own_counts(tiny_run):
    trees = tiny_run["counters"]["trees"] + 3 + 1    # warm-up, window, steady
    waves = program_counters.per_tree("grow.waves")
    assert len(waves) == trees and min(waves) >= 1
    rows = program_counters.rows_per_wave()
    assert rows >= ROWS and rows == int(rows)

    got = {name: reader(name).read(tiny_run) for name in NEW}
    # untraced: the mean over the run's trees
    assert got["grower.waves_per_tree"] == pytest.approx(sum(waves) / trees)
    assert 1 <= got["grower.waves_per_tree"] <= 30   # 31 leaves: 30 splits
    assert 0 < got["grower.route_useful_share"] <= 100
    assert 0 < got["kernels.hist_useful_share"] <= 100
    # every tree makes its root pass over all rows, and more
    assert 1 <= got["kernels.hist_passes_per_tree"] <= got["grower.waves_per_tree"] * rows / ROWS
    assert 1 <= got["compile.step_traces"] <= got["compile.step_executables"]
    assert got["setup.dataset_s"] > 0
    assert got["kernels.hist_exec_roofline"] is None     # nothing was traced


def test_a_traced_run_reads_the_traced_tree(tiny_run):
    """The traced tree follows the warm-up dispatches: its own record is
    read, not the mean. The kernel seconds here are made up (this is the
    CPU); only which record is picked and how it is combined is checked."""
    touched = program_counters.per_tree("grow.hist_rows_touched")
    waves = program_counters.per_tree("grow.waves")
    index = len(tiny_run["info"]["warmup_s"])
    traced = dict(tiny_run, trace={"class_s": {"matmul": 2.0, "custom": 0.0,
                                               "other": 1.0}})
    assert reader("grower.waves_per_tree").read(traced) == waves[index]
    passes = reader("kernels.hist_passes_per_tree").read(traced)
    assert passes == touched[index] / ROWS
    assert reader("kernels.hist_exec_roofline").read(traced) == pytest.approx(
        100.0 * tiny_run["work"]["root_floor_s"] * passes / 2.0)
    no_kernel = dict(tiny_run, trace={"class_s": {"matmul": 0.0, "custom": 0.0,
                                                  "other": 1.0}})
    assert reader("kernels.hist_exec_roofline").read(no_kernel) is None


def test_an_empty_registry_reads_as_nothing(tiny_run):
    from lightgbm_tpu import observability as obs
    saved = obs.get_registry()
    snapshot = (dict(saved._counters), dict(saved._gauges),
                dict(saved._summaries))
    saved.reset()
    try:
        traced = dict(tiny_run, trace={"class_s": {"matmul": 2.0, "custom": 0.0,
                                                   "other": 1.0}})
        for name in NEW:
            assert reader(name).read(tiny_run) is None, name
            assert reader(name).read(traced) is None, name
    finally:
        saved._counters, saved._gauges, saved._summaries = (
            dict(snapshot[0]), dict(snapshot[1]), dict(snapshot[2]))
