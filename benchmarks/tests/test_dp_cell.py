"""The four-chip cell on four forced host devices: a sound run of
``jobs/train_dp.py`` is correct against the one-table reference, a run in
which one shard's rows are left out of the reduce is not, nor is the bf16
control; and the three readers the cell brings read a per-device trace and
the program's collective counters, and nothing (None, never 0) where a run
has neither.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_dp_cell.py -q

The file asks the CPU backend for four devices before jax starts; run
beside files that started jax with fewer, its training tests skip.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

import run as harness  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "criteo67-255-dp4-train"
ROWS = 65536
CHIPS = 4


@pytest.fixture
def four_devices():
    import jax
    if len(jax.devices("cpu")) < CHIPS:
        pytest.skip("jax started with fewer than four host devices")


def tiny_ctx() -> dict:
    ctx = harness.resolve_cell(CELL)
    ctx["config"]["data"].update(rows=ROWS, block_rows=4096)
    ctx["config"]["params"].update(num_leaves=31, device="cpu", verbose=-1)
    ctx["traffic"]["sample_rows"] = ROWS
    ctx["traffic"]["scan"]["rows"] = [2000, ROWS]
    return harness.make_ctx(ctx, seed=2147483659, seconds=0.0, trace=False,
                            device={"platform": "cpu", "kind": "TPU v5 lite",
                                    "count": CHIPS},
                            log=lambda msg: None)


def job_module():
    job = harness.load_module(os.path.join(HERE, "jobs", "train_dp.py"),
                              "job_train_dp_test")
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    return job


def shard_left_out(job, shard: int = CHIPS - 1):
    """One shard's rows never reach the reduce: its block of the padding
    mask is zeroed, so its local histograms are empty while its rows are
    still routed and scored."""
    import jax.numpy as jnp
    sound, masked = job.train.dispatch, []

    def dispatch(bst, gbdt):
        if not masked:
            per = gbdt.num_data_padded // CHIPS
            rows = jnp.arange(gbdt.num_data_padded)
            keep = ((rows < shard * per) | (rows >= (shard + 1) * per))
            gbdt.pad_mask = gbdt.pad_mask * keep.astype(gbdt.pad_mask.dtype)
            gbdt.bag_mask = gbdt.pad_mask
            masked.append(True)
        return sound(bst, gbdt)
    return dispatch


def test_sound_run_is_correct_and_counts_a_shards_rows(four_devices):
    run = job_module().run(tiny_ctx())
    assert run["correct"], run["compared"]
    assert run["attempted"] >= 1 and run["failed"] == 0
    work = run["work"]
    assert (work["rows"], work["rows_table"], work["chips"]) == (ROWS // CHIPS, ROWS, CHIPS)
    assert run["counters"]["ingest"]["devices"] == CHIPS
    assert len(run["info"]["setup_parts_s"]["ingest_by_device"]) == CHIPS
    # the readers that divide the program's counts by the rows: a shard's
    passes = harness.load_module(
        os.path.join(HERE, "metrics", "kernels.hist_passes_per_tree.py"), "m_passes")
    assert 1.0 <= passes.read(run) <= run["info"]["leaves"][0]
    gb = harness.load_module(
        os.path.join(HERE, "metrics", "collectives.gb_per_tree.py"), "m_gb")
    assert gb.read(run) > 0


def test_a_shard_left_out_of_the_reduce_is_not_correct(four_devices):
    job = job_module()
    job.train.dispatch = shard_left_out(job)
    run = job.run(tiny_ctx())
    assert not run["correct"], run["info"]["numbers"]
    assert not run["compared"]["count_mismatch"]["ok"]


def test_bf16_control_is_not_correct(four_devices):
    from lib import compare
    from readings import in_place
    ctx = tiny_ctx()
    job = job_module()
    run = job.run(ctx)
    st = run["state"]
    out = job.refer(ctx["config"], ctx["traffic"], st["X"], st["y"],
                    st["program"]["valued"], st["score_before"], st["sample"],
                    ctx["seed"], precision="bf16")
    nums = compare.numbers(in_place(st["program"], out), st["ref"], st["y"][st["sample"]])
    correct, _ = compare.judge(nums, compare.load_limits(HERE, CELL))
    assert not correct, nums


# ------------------------------------------------------------- the readers

def reader(name: str):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               "metric_" + name.replace(".", "_"))


def recorded_two_planes():
    """Two device planes over one 1,000 ns window: each runs a matmul
    fusion, then waits in an all-reduce; the second also issues and waits
    for an asynchronous collective-permute."""
    hlo = lambda name, op: f"%{name} = f32[8]{{0}} {op}"    # noqa: E731
    first = [["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput, calls=%c", 0.0, 500.0],
             [hlo("all-reduce.3", "all-reduce(%fusion.1), channel_id=1"), 500.0, 100.0]]
    second = [["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput, calls=%c", 0.0, 300.0],
              [hlo("all-reduce.3", "all-reduce(%fusion.1), channel_id=1"), 300.0, 300.0],
              [hlo("collective-permute-start", "collective-permute-start(%x)"), 600.0, 10.0],
              [hlo("collective-permute-done", "collective-permute-done(%s)"), 700.0, 50.0]]
    from lib import xplane
    devices = {}
    for plane, events in (("/device:TPU:0", first), ("/device:TPU:1", second)):
        devices[plane] = [list(xplane.parse_hlo(text)) + [start, dur]
                          for text, start, dur in events]
    return {"devices": devices, "host": [["bench.traced", 0.0, 1000.0]]}


def test_readers_on_a_per_device_trace():
    job = job_module()
    trace = job.reduce_per_device(recorded_two_planes())
    assert sorted(trace["per_device"]) == ["/device:TPU:0", "/device:TPU:1"]
    run = {"trace": trace}
    # (100 + 300 + 50) ns of collectives over two planes of a 1,000 ns
    # window; the -start's 10 ns are not a wait
    assert reader("collectives.exposed_share").read(run) == pytest.approx(22.5)
    # busy 600 ns against 660 ns
    assert reader("device.busy_skew").read(run) == pytest.approx(6.0)
    assert reader("device.idle_share").read(run) == pytest.approx(37.0)


def test_readers_find_nothing_without_collectives_or_planes():
    from lib import xplane
    recorded = recorded_two_planes()
    one_chip = {"devices": {"/device:TPU:0": recorded["devices"]["/device:TPU:0"][:1]},
                "host": recorded["host"]}
    plain = xplane.reduce_events(one_chip)              # what jobs/train.py hands over
    for run in ({"trace": None}, {}, {"trace": plain},
                {"trace": job_module().reduce_per_device(one_chip)}):
        assert reader("collectives.exposed_share").read(run) is None
        assert reader("device.busy_skew").read(run) is None


def test_gb_per_tree_is_none_where_the_program_counted_no_collective():
    from lightgbm_tpu import observability as obs
    obs.reset_for_tests()
    assert reader("collectives.gb_per_tree").read({"trace": None}) is None
    reg = obs.get_registry()
    for moved in (4.0e9, 6.0e9):
        reg.summary("comm.bytes.psum_scatter_hist").observe(moved)
        reg.summary("comm.bytes.psum_root_scalars").observe(12.0)
    # untraced: the mean over the trees; traced: the traced tree's own
    assert reader("collectives.gb_per_tree").read({"trace": None}) == \
        pytest.approx(5.000000012)
    traced = {"trace": {"devices": 1}, "info": {"warmup_s": [0.1]}}
    assert reader("collectives.gb_per_tree").read(traced) == pytest.approx(6.000000012)
    obs.reset_for_tests()
