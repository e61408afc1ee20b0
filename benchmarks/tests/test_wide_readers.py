"""The three readers ``epsilon-255-train`` adds (``kernels.hist_acc_gb_per_tree``,
``grower.scan_useful_share``, ``setup.find_bins_s``) and the job it runs
behind (``jobs/train_resident.py``), on the CPU at a tiny size. The readers
read what ANY run of the program publishes, so the run is the narrow cell's
(2,000 columns take minutes a tree on the CPU); what is checked is which
record each reader picks and how it combines it, and that a program that
publishes nothing reads as nothing, never 0.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_wide_readers.py -q
"""
import os

import pytest

import run as harness
from lib import program_counters
from test_correct import tiny_ctx

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "epsilon-255-train"
NEW = {"kernels.hist_acc_gb_per_tree": "train_rate",
       "grower.scan_useful_share": "train_rate",
       "setup.find_bins_s": "setup_s"}


def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


def resident_job():
    job = harness.load_module(os.path.join(HERE, "jobs", "train_resident.py"),
                              "job_train_resident_test")
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    return job


@pytest.fixture(scope="module")
def tiny_run():
    """A whole run THROUGH ``train_resident`` (probe, then ``jobs/train.py``
    unchanged) of the narrow cell's table."""
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    logged = []
    ctx = dict(tiny_ctx("criteo67-255-train"), log=logged.append)
    run = resident_job().run(ctx)
    assert run["correct"]
    assert logged[0].startswith("probe: 32768 x 67 rows, residency=device")
    # the probe's seconds ride in the result, so setup_s can be read without
    assert 0 < run["info"]["setup_parts_s"]["probe"] < run["end_to_end"]["setup_s"]
    yield run
    obs.reset_for_tests()


def test_the_cell_declares_the_new_metrics_and_its_files_exist():
    ctx = harness.resolve_cell(CELL)
    declared = {m["name"]: m for m in ctx["per_layer"]}
    for name, moves in NEW.items():
        assert declared[name]["moves"] == moves
        assert declared[name]["workloads"] == [CELL]
        assert callable(reader(name).read)
    # every per-layer metric the cell lists has a reader, and the cell
    # reports every end-to-end metric the benchmark has
    for name in declared:
        assert callable(reader(name).read), name
    assert len(declared) == 19
    assert [m["name"] for m in ctx["end_to_end"]] == [
        "train_rate", "hbm_peak_gib", "setup_s"]
    assert ctx["traffic"]["job"] == "train_resident"
    assert ctx["config"]["reduced"] == []
    data = ctx["config"]["data"]
    assert (data["rows"], sum(c["n"] for c in data["columns"])) == (400000, 2000)
    assert os.path.isfile(os.path.join(HERE, "limits", CELL + ".json"))


def test_readers_read_the_programs_own_counts(tiny_run):
    acc = program_counters.per_tree("grow.hist_acc_bytes")
    slots = program_counters.per_tree("grow.scan_slots")
    held = program_counters.per_tree("grow.scan_slots_pending")
    trees = tiny_run["counters"]["trees"] + 3 + 1    # warm-up, window, steady
    assert len(acc) == len(slots) == len(held) == trees
    assert all(0 < h <= s for h, s in zip(held, slots))
    got = {name: reader(name).read(tiny_run) for name in NEW}
    # untraced: the mean over the run's trees
    assert got["kernels.hist_acc_gb_per_tree"] == pytest.approx(
        sum(acc) / trees / 1e9)
    assert got["grower.scan_useful_share"] == pytest.approx(
        100.0 * (sum(held) / trees) / (sum(slots) / trees))
    assert 0 < got["grower.scan_useful_share"] <= 100
    assert got["setup.find_bins_s"] == program_counters.gauge(
        "setup.dataset_find_bins_s") > 0
    # traced: the traced tree's own record
    index = len(tiny_run["info"]["warmup_s"])
    traced = dict(tiny_run, trace={"class_s": {"matmul": 1.0}})
    assert reader("kernels.hist_acc_gb_per_tree").read(traced) == acc[index] / 1e9
    assert reader("grower.scan_useful_share").read(traced) == pytest.approx(
        100.0 * held[index] / slots[index])


def test_an_empty_registry_reads_as_nothing(tiny_run):
    from lightgbm_tpu import observability as obs
    saved = obs.get_registry()
    snapshot = (dict(saved._counters), dict(saved._gauges),
                dict(saved._summaries))
    saved.reset()
    try:
        traced = dict(tiny_run, trace={"class_s": {"matmul": 1.0}})
        for name in NEW:
            assert reader(name).read(tiny_run) is None, name
            assert reader(name).read(traced) is None, name
    finally:
        saved._counters, saved._gauges, saved._summaries = (
            dict(snapshot[0]), dict(snapshot[1]), dict(snapshot[2]))


def test_the_probe_refuses_a_program_that_would_stream(monkeypatch):
    """A program whose pre-flight estimate sends the table to
    ``tpu_residency=stream`` (the parent of PR 30 at 2,000 columns) ends the
    run non-zero at the probe, before the table is built."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    monkeypatch.setattr(GBDT, "_resolve_residency",
                        lambda self, config, **kw: "stream")
    job = resident_job()
    built = []
    monkeypatch.setattr(job.train, "run", lambda ctx: built.append(ctx))
    ctx = dict(tiny_ctx("criteo67-255-train"), log=lambda msg: None)
    ctx["config"]["data"]["rows"] = 4096
    with pytest.raises(SystemExit) as refused:
        job.run(ctx)
    assert refused.value.code not in (0, None)
    assert "stream" in str(refused.value.code) and not built
