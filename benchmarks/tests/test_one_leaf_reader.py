"""The reader ISSUE 37 adds (``kernels.hist_one_leaf_share``), on records
made by hand: it reads the program's own summaries, so a registry that
holds them is all it needs. A program from before the one-leaf form
publishes no ``grow.hist_rows_one_leaf`` and reads as nothing, never 0.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_one_leaf_reader.py -q
"""
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kernels.hist_one_leaf_share"
CELLS = ["criteo67-255-train", "epsilon-255-train", "criteo67-255-dp4-train",
         "criteo67-255-goss-train"]


def reader():
    return harness.load_module(os.path.join(HERE, "metrics", NAME + ".py"),
                               "metric_" + NAME.replace(".", "_"))


@pytest.fixture
def registry():
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    yield obs.get_registry()
    obs.reset_for_tests()


def run_record(traced: bool) -> dict:
    return {"info": {"warmup_s": [1.0, 1.0, 1.0]},
            "trace": {"class_s": {"matmul": 1.0}} if traced else None,
            "work": {"rows": 1000}}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_declares_the_metric(cell):
    declared = {m["name"]: m for m in harness.resolve_cell(cell)["per_layer"]}
    assert declared[NAME] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels", "moves": "train_rate",
        "workloads": CELLS}
    assert callable(reader().read)


@pytest.mark.parametrize("traced", [False, True])
def test_a_record_without_the_count_reads_as_nothing(registry, traced):
    # the parent: rows touched, no one-leaf count
    for touched in (3650, 3600, 3700, 3650, 3640):
        registry.summary("grow.hist_rows_touched").observe(touched)
    assert reader().read(run_record(traced)) is None


def test_a_record_with_the_count_reads_the_share(registry):
    touched = (3650, 3600, 3700, 3000, 3640)
    one_leaf = (1400, 1350, 1500, 1200, 1390)
    for t, o in zip(touched, one_leaf):
        registry.summary("grow.hist_rows_touched").observe(t)
        registry.summary("grow.hist_rows_one_leaf").observe(o)
    # untraced: the means over the run's trees
    assert reader().read(run_record(False)) == pytest.approx(
        100.0 * sum(one_leaf) / sum(touched))
    # traced: the traced tree's own record (it follows three warm-ups)
    assert reader().read(run_record(True)) == pytest.approx(100.0 * 1200 / 3000)


def test_no_rows_touched_reads_as_nothing(registry):
    registry.summary("grow.hist_rows_one_leaf").observe(0)
    assert reader().read(run_record(False)) is None
