"""The seven readers ISSUE 38 adds (``step.host_ms``, ``step.stall_share``,
``step.gc_share``, ``setup.program_s``, ``setup.unnamed_s``,
``compile.step_first_call_s``, ``compile.step_trace_s``): on records made
by hand, since they read the program's own registry and a registry that
holds the records is all they need, and on a whole CPU run of one cell at
a tiny size (``rehearse.py``'s). A program from before the records reads
as nothing, never 0.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_host_readers.py -q
"""
import os
import statistics

import pytest

import run as harness
from lib import program_counters
from test_correct import job_module, tiny_ctx

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["criteo67-255-train", "epsilon-255-train", "criteo67-255-dp4-train",
         "criteo67-255-goss-train"]
NEW = {"step.host_ms": ("ms", "step", "train_rate"),
       "step.stall_share": ("%", "step", "train_rate"),
       "step.gc_share": ("%", "step", "train_rate"),
       "setup.program_s": ("s", "set-up", "setup_s"),
       "setup.unnamed_s": ("s", "set-up", "setup_s"),
       "compile.step_first_call_s": ("s", "compile", "setup_s"),
       "compile.step_trace_s": ("s", "compile", "setup_s")}
WARMUPS = 3


def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


@pytest.fixture
def registry():
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    yield obs.get_registry()
    obs.reset_for_tests()


def run_record(window: int, traced: bool = False) -> dict:
    return {"info": {"warmup_s": [1.0] * WARMUPS},
            "spans": {"dispatch_s": [0.7] * window},
            "trace": {"class_s": {"matmul": 1.0}} if traced else None}


def plant(registry, calls):
    """``calls``: (host_s, seconds the caller then spent, gc seconds in
    that interval) of each call in order, as ``observability.step_call``
    would have recorded them."""
    gap = gc = 0.0
    for host, after, collector in calls:
        registry.summary("step.host_s").observe(host)
        registry.summary("step.gap_s").observe(gap)
        registry.summary("step.gc_s").observe(gc)
        gap, gc = after, collector


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_declares_the_seven(cell):
    declared = {m["name"]: m for m in harness.resolve_cell(cell)["per_layer"]}
    for name, (unit, layer, moves) in NEW.items():
        assert declared[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": CELLS}
        assert callable(reader(name).read)


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_record_reads_as_nothing(registry, name, traced):
    # the parent: its own counters and gauges, none of the new ones
    registry.inc("compile.step_executables")
    registry.gauge("setup.dataset_find_bins_s").set(0.7)
    registry.summary("grow.waves").observe(14)
    assert reader(name).read(run_record(40, traced)) is None


def test_a_planted_stall_reads_as_its_excess_over_the_median(registry):
    steady = (0.002, 0.698, 0.0)                 # a dispatch of 0.7 s
    window = [steady] * 40
    window.insert(17, (0.002, 1.998, 0.0))       # one of 2.0 s, all in the block
    plant(registry, [(9.0, 1.0, 0.2)] * WARMUPS + window + [steady])
    run = run_record(41)
    # the window's last dispatch has no length: 39 x 0.7 + 2.0, excess 1.3
    share = reader("step.stall_share").read(run)
    assert share == pytest.approx(100 * 1.3 / (39 * 0.7 + 2.0))
    assert 4.2 <= share <= 4.5
    assert reader("step.host_ms").read(run) == pytest.approx(2.0)
    assert reader("step.gc_share").read(run) == 0.0
    # no stall: nothing over 1.5 x the median
    registry.reset()
    plant(registry, [(9.0, 1.0, 0.0)] * WARMUPS + [steady] * 41)
    assert reader("step.stall_share").read(run_record(40)) == 0.0


def test_warm_ups_and_the_traced_dispatch_are_not_the_windows(registry):
    steady = (0.002, 0.698, 0.0)
    warm = (25.0, 2.0, 0.5)              # a first call: compile, collections
    traced = (0.002, 30.0, 1.0)          # then the trace's reduction
    plant(registry, [warm] * WARMUPS + [traced] + [steady] * 21)
    run = run_record(20, traced=True)
    assert reader("step.stall_share").read(run) == 0.0
    assert reader("step.gc_share").read(run) == 0.0
    assert reader("step.host_ms").read(run) == pytest.approx(2.0)
    # read as an untraced run, the same series puts the traced call first in
    # the window: its 30 s are then a stall, which is how a wrong index shows
    assert reader("step.stall_share").read(run_record(20)) > 50


def test_the_windows_last_gap_is_left_out(registry):
    steady = (0.002, 0.698, 0.01)
    last = (0.002, 12.0, 3.0)            # then the score's fetch, the steady tree
    plant(registry, [(9.0, 1.0, 0.0)] * WARMUPS + [steady] * 19 + [last, steady])
    run = run_record(20)
    assert reader("step.stall_share").read(run) == 0.0
    assert reader("step.gc_share").read(run) == pytest.approx(100 * 0.01 / 0.7)
    # the median of step.host_s is over all twenty
    assert reader("step.host_ms").read(run) == pytest.approx(2.0)
    # a window of one dispatch has no length to read
    registry.reset()
    plant(registry, [(9.0, 1.0, 0.0)] * WARMUPS + [last, steady])
    assert reader("step.stall_share").read(run_record(1)) is None
    assert reader("step.gc_share").read(run_record(1)) is None
    assert reader("step.host_ms").read(run_record(1)) == pytest.approx(2.0)


def test_a_series_shorter_than_the_window_reads_as_nothing(registry):
    plant(registry, [(0.002, 0.698, 0.0)] * 10)
    for name in ("step.host_ms", "step.stall_share", "step.gc_share"):
        assert reader(name).read(run_record(20)) is None


def test_set_up_and_compile_readers_add_their_parts(registry):
    registry.gauge("setup.dataset_construct_s").set(1.5)
    assert reader("setup.program_s").read(run_record(4)) is None   # one whole
    registry.gauge("setup.booster_init_s").set(6.25)
    registry.gauge("setup.unnamed_s").set(0.125)
    assert reader("setup.program_s").read(run_record(4)) == 7.75
    assert reader("setup.unnamed_s").read(run_record(4)) == 0.125
    registry.counter("compile.step_first_call_s").inc(3.5)
    registry.counter("compile.step_trace_s").inc(0.5)
    assert reader("compile.step_trace_s").read(run_record(4)) is None
    registry.counter("compile.step_lower_s").inc(0.25)
    assert reader("compile.step_first_call_s").read(run_record(4)) == 3.5
    assert reader("compile.step_trace_s").read(run_record(4)) == 0.75


@pytest.fixture(scope="module")
def tiny_run():
    """A whole run of ``criteo67-255-train`` on the CPU at a tiny size, with
    a window long enough to hold two dispatches or more."""
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    ctx = dict(tiny_ctx("criteo67-255-train"), seconds=4.0)
    run = job_module().run(ctx)
    assert run["correct"]
    yield run
    obs.reset_for_tests()


def test_the_seven_on_a_whole_run(tiny_run):
    n = len(tiny_run["spans"]["dispatch_s"])
    assert n >= 2
    host = program_counters.per_tree("step.host_s")
    gap = program_counters.per_tree("step.gap_s")
    assert len(host) == len(gap) == WARMUPS + n + 1     # and the steady tree
    got = {name: reader(name).read(tiny_run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["step.host_ms"] == pytest.approx(
        1000 * statistics.median(host[WARMUPS:WARMUPS + n]))
    # the program's entry-to-entry record and the harness's clock time the
    # same dispatches
    inside = [host[k] + gap[k + 1] for k in range(WARMUPS, WARMUPS + n - 1)]
    outside = tiny_run["spans"]["dispatch_s"][:-1]
    assert sum(inside) == pytest.approx(sum(outside), rel=0.05)
    assert 0 <= got["step.stall_share"] < 100 and 0 <= got["step.gc_share"] < 100
    assert 0 <= got["setup.unnamed_s"] < got["setup.program_s"]
    assert 0 < got["compile.step_trace_s"] <= got["compile.step_first_call_s"]
    # the step's one executable was gained by set-up's first dispatch
    assert program_counters.counter("compile.step_executables") == 1
    assert got["compile.step_first_call_s"] <= tiny_run["info"]["warmup_s"][0]
