"""`correct` has to come out FALSE when it should: the control and the faults.

Run by hand or with pytest (the repo's tier-1 command runs ``tests/`` only):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Each test drives the whole of a run except the harness's look for a chip
(jobs/train.run on the CPU at a tiny size, with the cell's own limits):

  sound     the program as it is: correct
  control   the reference in the program's place at bfloat16: not correct
  stale     a step that returns its state unchanged: not correct
  half      half of the rows left out, leaf values from the rest: not correct
  altered   a leaf value altered where it is produced (x1.02): not correct
  late(...) each of the three from the window's first dispatch on, the
            followed trees sound: the steady tree and score_gap have to see it
  (the exchange between chips does not exist in a one-chip cell)
"""
import os

import numpy as np
import pytest

import run as harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["criteo67-255-train"]
ROWS = 60000
WARMUP = 3        # traffic/train.json: the dispatches set-up makes, all followed


def tiny_ctx(cell: str) -> dict:
    ctx = harness.resolve_cell(cell)
    ctx["config"]["data"].update(rows=ROWS, block_rows=4096)
    ctx["config"]["params"].update(num_leaves=31, device="cpu", verbose=-1)
    ctx["traffic"]["sample_rows"] = ROWS      # every row: the scan of roots is exact
    ctx["traffic"]["scan"]["rows"] = [2000, ROWS]
    return harness.make_ctx(ctx, seed=2147483659, seconds=0.0, trace=False,
                            device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
                            log=lambda msg: None)


def job_module():
    job = harness.load_module(os.path.join(HERE, "jobs", "train.py"), "job_train_test")
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    return job


def stale(job):
    def dispatch(bst, gbdt):
        kept = gbdt.score + 0
        bst.update()
        gbdt.score = kept
        return 0.0
    return dispatch


def half(job):
    import jax.numpy as jnp
    masked = []

    def dispatch(bst, gbdt):
        if not masked:
            keep = (jnp.arange(gbdt.num_data_padded) % 2).astype(gbdt.pad_mask.dtype)
            gbdt.pad_mask = gbdt.pad_mask * keep
            gbdt.bag_mask = gbdt.pad_mask
            masked.append(True)
        bst.update()
        return 0.0
    return dispatch


def altered(job):
    def dispatch(bst, gbdt):
        bst.update()
        tree = gbdt.models[-1][0]
        gbdt.models[-1][0] = tree._replace(leaf_value=tree.leaf_value * 1.02)
        return 0.0
    return dispatch


def late(fault):
    """``fault`` from the first dispatch of the window on."""
    def make(job):
        sound, broken = job.dispatch, fault(job)

        def dispatch(bst, gbdt):
            return (sound if gbdt.iter_ < WARMUP else broken)(bst, gbdt)
        return dispatch
    make.__name__ = "late_" + fault.__name__
    return make


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    run = job_module().run(tiny_ctx(cell))
    assert run["correct"], run["compared"]
    assert run["attempted"] >= 1 and run["failed"] == 0


@pytest.mark.parametrize("fault", [stale, half, altered,
                                   late(stale), late(half), late(altered)],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    job = job_module()
    job.dispatch = fault(job)
    ctx = tiny_ctx(CELLS[0])
    assert ctx["traffic"]["warmup_dispatches"] == WARMUP
    run = job.run(ctx)
    assert not run["correct"], (fault.__name__, run["info"]["numbers"])


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(cell):
    from lib import compare
    from readings import in_place
    ctx = tiny_ctx(cell)
    job = job_module()
    run = job.run(ctx)
    st = run["state"]
    out = job.refer(ctx["config"], ctx["traffic"], st["X"], st["y"], st["program"]["valued"],
                    st["score_before"], st["sample"], ctx["seed"], precision="bf16")
    nums = compare.numbers(in_place(st["program"], out), st["ref"], st["y"][st["sample"]])
    correct, compared = compare.judge(nums, compare.load_limits(HERE, cell))
    assert not correct, nums


def test_a_missing_program_or_chip_gives_no_result(capsys):
    # on this CPU the harness's own device check must refuse to run
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code != 0
    assert capsys.readouterr().out.strip() == ""


def test_seeds_hold_the_same_table_with_its_columns_in_another_order():
    from lib import datagen
    data = dict(harness.resolve_cell(CELLS[0])["config"]["data"], block_rows=1024)
    Xa, ya = datagen.generate(data, 10240, 1)
    Xb, yb = datagen.generate(data, 10240, 2147483659)
    Xc, _ = datagen.generate(data, 10240, 1)
    assert np.array_equal(Xa, Xc)
    assert not np.array_equal(Xa, Xb)
    pa, pb = datagen.column_places(data, 1), datagen.column_places(data, 2147483659)
    assert np.array_equal(Xa[:, pa], Xb[:, pb]) and np.array_equal(ya, yb)
