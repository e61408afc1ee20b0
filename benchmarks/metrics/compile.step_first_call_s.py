"""Seconds of the training step's calls that gained an executable (the
counter ``compile.step_first_call_s``, timed at the program's dispatch
around the jitted call): Python tracing, lowering, and the backend's
compile or cache load, all of it inside set-up (a program that compiles
or loads in the window ends the run). Warm: a trace and a load, seconds;
cold: the whole compile. ``compile.seconds`` counts backend compiles of
every program and none of the tracing. None on a program without the
counter."""
from lib import program_counters


def read(run: dict):
    return program_counters.counter("compile.step_first_call_s")
