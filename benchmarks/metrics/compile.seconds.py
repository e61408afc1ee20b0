"""Seconds jax spent in backend compiles during set-up (jax.monitoring, see
lib/compile_meter.py). A persistent-cache hit counts only its retrieval, so a
warm run reads a few seconds and a cold one minutes."""


def read(run: dict):
    return run["spans"].get("compile_s")
