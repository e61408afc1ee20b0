"""Test harness config: pin the CPU backend with 8 virtual devices so
every parallel strategy (tree_learner=data|feature|voting) is exercised
without TPU hardware — the capability the reference never had (its MPI path
was only ever CI-tested single-process, SURVEY.md §4). On the chip the
program runs through ``chip_smoke.py`` instead, one process per chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lightgbm_tpu.utils.hermetic import force_cpu_backend  # noqa: E402

force_cpu_backend(device_count=8)
import jax  # noqa: E402

# Persistent compile cache: the suite is dominated by XLA compiles of the
# train-step program; warm reruns skip them entirely.
from lightgbm_tpu.utils.cache import resolve_compile_cache  # noqa: E402

resolve_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process cluster, big data)")
    config.addinivalue_line(
        "markers", "tpu: requires a real TPU backend (Mosaic lowering, "
                   "device transfer semantics); skipped under the hermetic "
                   "CPU harness / JAX_PLATFORMS=cpu")
    config.addinivalue_line(
        "markers", "chaos: fault-injection suite (robustness/chaos.py) — "
                   "run via `make chaos` with a pinned LGBM_TPU_CHAOS_SEED; "
                   "fast enough to ride in tier-1 too")


def pytest_collection_modifyitems(config, items):
    import pytest
    if jax.default_backend() == "tpu":
        return
    skip_tpu = pytest.mark.skip(
        reason="requires real TPU hardware (hermetic CPU harness)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)
