"""Seconds of ``setup.program_s`` that no child span names: the gauge
``setup.unnamed_s`` (the two wholes minus their direct children, set when
``booster.init`` ends). Under a second, set-up's table in PERF.md is
complete; a later PR that adds seconds outside every span shows here.
None on a program without the gauge."""
from lib import program_counters


def read(run: dict):
    return program_counters.gauge("setup.unnamed_s")
