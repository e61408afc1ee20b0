"""RecompileGuard tests: the enforced invariant that a steady-state
training loop dispatches one compiled executable — the runtime half of the
analysis subsystem (lightgbm_tpu/analysis/guards.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis.guards import (GuardViolation, RecompileGuard,
                                          recompile_guard)


def _jitted_double():
    return jax.jit(lambda x: x * 2.0)


def test_stable_loop_passes():
    f = _jitted_double()
    f(jnp.ones(16))
    g = RecompileGuard(label="stable")
    g.register(f, "f")
    with g:
        g.mark_warm()
        for _ in range(5):
            f(jnp.ones(16))
    rep = g.report()
    assert rep["post_warmup_cache_misses"] == 0
    assert rep["misses_by_entrypoint"] == {"f": 0}


def test_shape_change_after_warmup_raises():
    f = _jitted_double()
    f(jnp.ones(16))
    g = RecompileGuard(label="leaky")
    g.register(f, "f")
    with pytest.raises(GuardViolation, match="recompiled"):
        with g:
            g.mark_warm()
            f(jnp.ones(32))          # new shape -> new executable


def test_weak_type_change_is_a_miss():
    # the classic silent leak: a python-scalar op flips weak_type in the
    # signature and recompiles even though shape/dtype look identical
    f = jax.jit(lambda x: x + 1)
    f(jnp.arange(4.0))
    g = RecompileGuard(label="weak")
    g.register(f, "f")
    with pytest.raises(GuardViolation):
        with g:
            g.mark_warm()
            f(np.float32(3.0) * np.ones(4, np.float32))  # committed dtype,
            # same shape — but a distinct avals signature than jnp.arange


def test_fail_false_records_instead_of_raising():
    f = _jitted_double()
    f(jnp.ones(8))
    g = RecompileGuard(label="record", fail=False)
    g.register(f, "f")
    with g:
        g.mark_warm()
        f(jnp.ones(64))
    assert g.report()["post_warmup_cache_misses"] == 1


def test_transfer_counting_and_disallow():
    f = _jitted_double()
    y = f(jnp.ones(4))
    with recompile_guard([f], label="sync", fail=False) as g:
        y.sum().item()
        float(y.sum())
    assert g.transfers >= 2
    with pytest.raises(GuardViolation, match="device->host"):
        with recompile_guard([f], label="strict", fail=False,
                             disallow_transfers=True):
            float(y.sum())
    # patched surface restored on exit
    assert float(y.sum()) == 8.0


def test_register_rejects_unjitted():
    g = RecompileGuard()
    with pytest.raises(TypeError, match="_cache_size"):
        g.register(lambda x: x)


def test_booster_steady_state_holds():
    """5 post-warm-up boosting iterations reuse ONE compiled step — the
    in-suite twin of `bench.py --smoke`."""
    rng = np.random.RandomState(0)
    X = rng.rand(2000, 8).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    params = dict(objective="binary", num_leaves=15, max_bin=63,
                  learning_rate=0.1, min_data_in_leaf=10, verbose=-1,
                  metric="none")
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y,
                                                           params=params))
    for _ in range(2):
        bst.update()
    np.asarray(bst._gbdt.score).sum()
    guard = RecompileGuard(label="train")
    guard.register(bst._gbdt._step_fn, "train_step")
    with guard:
        guard.mark_warm()
        for _ in range(5):
            bst.update()
        np.asarray(bst._gbdt.score).sum()
    assert guard.report()["post_warmup_cache_misses"] == 0


@pytest.mark.tpu
def test_transfer_guard_counts_np_asarray_on_device():
    """np.asarray on a DEVICE array must route through __array__ (no host
    buffer protocol) and be counted — only meaningful on real TPU, where
    the sync actually crosses the wire; the CPU backend converts zero-copy
    and legitimately bypasses the counter."""
    f = _jitted_double()
    y = f(jnp.ones(4))
    with recompile_guard([f], fail=False) as g:
        np.asarray(y)
    assert g.transfers >= 1
