"""Times jax traced the training step in this process, counted in the
step's own Python body (``compile.step_traces``): each trace past the
first is a compile or a cache load the booster pays again. None when the
program does not count them."""
from lib import program_counters


def read(run: dict):
    return program_counters.counter("compile.step_traces")
