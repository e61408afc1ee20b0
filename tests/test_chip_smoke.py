"""What a CPU can check about the chip path: where the compile cache goes,
that chip_smoke.py refuses to run without a TPU, and that importing the
package takes no backend (a chip belongs to one process — launchers such as
`bench.py --multichip` import helpers and must leave the TPU to the child).
"""
import os
import subprocess
import sys

import jax

from lightgbm_tpu.utils import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_resolver_env_set_sets_nothing(monkeypatch, tmp_path):
    outside = str(tmp_path / "outside_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    assert cache.resolve_compile_cache() == outside
    assert updates == []


def test_cache_resolver_unset_uses_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.resolve_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "train:" not in r.stdout          # stopped before training anything
    assert "no accelerator" in r.stderr


def test_importing_the_package_takes_no_backend():
    code = ("import lightgbm_tpu, lightgbm_tpu.utils.cache, "
            "lightgbm_tpu.utils.hermetic, bench, chip_smoke;"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge.backends_are_initialized()")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
