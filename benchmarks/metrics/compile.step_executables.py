"""Executables the training step's jit cache came to hold in this process
(``compile.step_executables``, counted at the program's dispatch): each is
a compile or a cache load that set-up paid for. One per booster is the
least; beside ``compile.step_traces`` it tells a recompile that retraced
from one that reused the trace (an argument that only went from uncommitted
to committed). None when the program does not count them."""
from lib import program_counters


def read(run: dict):
    return program_counters.counter("compile.step_executables")
