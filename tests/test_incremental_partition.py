"""How a compacted histogram pass gets its slot-grouped row index
(grower.py ``grow_tree``, phase ``wave.partition``).

Two arms, pinned BIT-identical to each other:

- the default (``tpu_incremental_partition=false``, since PR 28): nothing
  is carried; a wave whose pass is compacted builds the index with ONE
  stable sort of the rows by pending slot, inside the compacted arm of the
  wave's ``cond``; a streamed wave builds nothing;
- the carried partition (``tpu_incremental_partition=true``; GrowState.perm,
  the reference's DataPartition analog): re-partitioned in EVERY wave by
  gather + cumsums + a row-sized scatter. On the v5e that scatter hides a
  sort of its own and the arm cost half the tree (PERF.md, PR 28); it stays
  as the parity oracle.

Pins: the wave body holds at most one row-sized sort, in the compacted arm,
and no row-sized scatter or cumsum (contract T001; the carried arm is the
target that violates; the COMPILED step's row-sized sorts are counted in
tests/test_named_scopes.py); trees are bit-identical
between the arms: serial and tree_learner=data, bagging + feature_fraction
RNG, forced compaction (tpu_compact_frac=1.0), u4 bit-packed code mode,
exact leaf-wise ordering (tpu_wave_size=1), tree_batch>1, checkpoint-resume
mid-tree-batch, and the mixed XLA/Pallas kernel dispatch (interpret mode);
the config knob round-trips.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.grower import GrowerSpec, grow_tree


def _make_binary(n=3000, f=10, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    logit = X[:, 0] - 0.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.randn(n).astype(np.float32) * 0.2 > 0.3).astype(
        np.float32)
    return X, y


# tpu_compact_frac=1.0 forces the compacted pass on every wave after the
# root — the incremental remap must carry the whole tree, not just the tail
BASE = dict(objective="binary", num_leaves=31, learning_rate=0.1,
            min_data_in_leaf=3, device="cpu", verbose=-1, seed=5,
            bagging_fraction=0.7, bagging_freq=2, feature_fraction=0.8,
            tpu_compact_frac=1.0, metric="none")


def _train(X, y, incremental, rounds=8, **extra):
    params = dict(BASE, tpu_incremental_partition=incremental, **extra)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds)


def _assert_identical(b1, b2, X):
    np.testing.assert_array_equal(b1.predict(X), b2.predict(X))
    np.testing.assert_array_equal(b1.predict(X, raw_score=True),
                                  b2.predict(X, raw_score=True))
    assert len(b1.trees) == len(b2.trees)
    for t1, t2 in zip(b1.trees, b2.trees):
        np.testing.assert_array_equal(t1.leaf_value, t2.leaf_value)
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)


# ---------------------------------------------------------------- jaxpr pin
# The wave-loop pin lives in the trace-contract registry (contract T001,
# analysis/contracts/entries.py) — this test asserts THROUGH the registry,
# so the test and `python -m lightgbm_tpu.analysis --trace` check the same
# predicate via one implementation.

@pytest.mark.parametrize("shape_class,expect_sort",
                         [("serial", True), ("serial_carried", False)])
def test_wave_loop_jaxpr_sort_presence(shape_class, expect_sort):
    """The default wave body carries ONE sort primitive (the compacted
    arm's) and no row-sized scatter; the carried arm carries no sort
    primitive at all — and a row-sized scatter every wave, in which the
    TPU's compiler hides a sort that no jaxpr walk can see (the device
    trace did: PERF.md, PR 27)."""
    from lightgbm_tpu.analysis.contracts import (CONTRACTS, build_program,
                                                 evaluate, evaluate_target)
    from lightgbm_tpu.analysis.contracts import jaxpr_utils as ju
    import lightgbm_tpu.analysis.contracts.entries  # noqa: F401

    program = build_program("grower.wave_body", shape_class)
    assert ju.count_primitive(program.jaxpr, "sort") == int(expect_sort)
    # and the registered contract reaches the same verdict: no findings,
    # on the clean arm OR the violates arm (whose failure is expected)
    c = CONTRACTS["T001"]
    t = next(t for t in c.targets if t.shape_class == shape_class)
    assert evaluate(c, t, program) == []
    assert bool(evaluate_target(c, program)) == (t.expect == "violates")


@pytest.mark.parametrize("num_slots", [25, 128])
def test_rows_by_slot_is_the_stable_order(num_slots):
    """One sort gives the stable order: by slot, ascending row within a
    slot, rows of no pending leaf last — through the one-word key (slots
    below 2^7) and through the (slot, row) pair sort alike."""
    from lightgbm_tpu.grower import _rows_by_slot
    rng = np.random.RandomState(num_slots)
    slot = np.where(rng.rand(5000) < 0.3,
                    rng.randint(0, num_slots, 5000), -1).astype(np.int32)
    want = np.argsort(np.where(slot >= 0, slot, num_slots), kind="stable")
    got = jax.jit(_rows_by_slot, static_argnums=1)(jnp.asarray(slot),
                                                   num_slots)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------------- bit-identity pins

@pytest.mark.slow
@pytest.mark.parametrize("tree_learner", ["serial", "data"])
def test_incremental_vs_legacy_bit_identical(tree_learner):
    X, y = _make_binary()
    b_inc = _train(X, y, True, tree_learner=tree_learner)
    b_leg = _train(X, y, False, tree_learner=tree_learner)
    _assert_identical(b_inc, b_leg, X)


@pytest.mark.slow
def test_incremental_vs_legacy_u4_code_mode():
    """max_bin=15 engages the u4 nibble-packed row layout — the compacted
    gather's unpack must see the identical byte stream through the
    position remap."""
    X, y = _make_binary(seed=11)
    b_inc = _train(X, y, True, max_bin=15)
    b_leg = _train(X, y, False, max_bin=15)
    _assert_identical(b_inc, b_leg, X)


@pytest.mark.slow
def test_incremental_vs_legacy_exact_leafwise():
    """tpu_wave_size=1 (the reference's one-leaf-at-a-time ordering) takes
    maximally many waves — the partition survives the longest carry chains."""
    X, y = _make_binary(seed=3)
    b_inc = _train(X, y, True, tpu_wave_size=1, rounds=4)
    b_leg = _train(X, y, False, tpu_wave_size=1, rounds=4)
    _assert_identical(b_inc, b_leg, X)


@pytest.mark.parametrize("tree_learner", ["serial", "data"])
@pytest.mark.slow
def test_incremental_tree_batch_bit_identical(tree_learner):
    """tree_batch>1 fuses whole iterations under lax.scan — the per-tree
    partition reset (identity permutation at tree start) must hold inside
    the scan carry too. rounds=10 with K=4 exercises the final partial
    batch."""
    X, y = _make_binary()
    b_inc = _train(X, y, True, tree_learner=tree_learner, tree_batch=4,
                   rounds=10)
    b_leg = _train(X, y, False, tree_learner=tree_learner, tree_batch=4,
                   rounds=10)
    _assert_identical(b_inc, b_leg, X)
    # and against the unfused incremental run: K>1 stays bit-identical to
    # K=1 with the new carry
    b_inc1 = _train(X, y, True, tree_learner=tree_learner, tree_batch=1,
                    rounds=10)
    np.testing.assert_array_equal(b_inc.predict(X), b_inc1.predict(X))


@pytest.mark.slow
def test_incremental_checkpoint_resume_mid_tree_batch(tmp_path):
    """Interrupt a batched incremental run at a batch boundary, resume it,
    and land bit-identical to BOTH the uninterrupted incremental run and
    the legacy-partition run."""
    X, y = _make_binary()
    ck = str(tmp_path / "ck")
    params = dict(BASE, tpu_incremental_partition=True, tree_batch=4,
                  checkpoint_dir=ck, checkpoint_interval=4)
    full = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=12)
    lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=8)
    resumed = lgb.train(dict(params, resume_from="auto"),
                        lgb.Dataset(X, label=y), num_boost_round=12)
    np.testing.assert_array_equal(full.predict(X), resumed.predict(X))
    legacy = lgb.train(dict(BASE, tpu_incremental_partition=False,
                            tree_batch=4),
                       lgb.Dataset(X, label=y), num_boost_round=12)
    np.testing.assert_array_equal(full.predict(X), legacy.predict(X))


@pytest.mark.slow
def test_incremental_mixed_kernel_interpret(monkeypatch):
    """The mixed dispatch routes COMPACTED passes through the Pallas kernel
    — its chunk gather must read the carried permutation through the same
    position remap (interpret mode on the CPU harness)."""
    from lightgbm_tpu.ops import pallas_histogram as ph
    monkeypatch.setattr(ph, "_INTERPRET", True)
    X, y = _make_binary(n=2048, seed=9)
    b_inc = _train(X, y, True, tpu_hist_kernel="mixed", rounds=4)
    b_leg = _train(X, y, False, tpu_hist_kernel="mixed", rounds=4)
    _assert_identical(b_inc, b_leg, X)


def test_incremental_off_when_row_compact_off():
    """row_compact=false never builds the permutation carry (perm stays a
    None pytree leaf) and still trains; the knob round-trips through
    Config, and the default is the sort rebuild."""
    from lightgbm_tpu.config import Config
    assert Config.from_params({}).tpu_incremental_partition is False
    assert Config.from_params(
        dict(tpu_incremental_partition=True)).tpu_incremental_partition \
        is True
    X, y = _make_binary(n=800)
    b = _train(X, y, True, tpu_row_compact=False, rounds=3)
    b2 = _train(X, y, False, tpu_row_compact=False, rounds=3)
    _assert_identical(b, b2, X)
