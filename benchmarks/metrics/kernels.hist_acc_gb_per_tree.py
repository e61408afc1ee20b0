"""GB the histogram passes of one tree moved through the f32 accumulator:
the program's ``grow.hist_acc_bytes`` (every chunk of every pass reads and
writes the [F, B, S*ch] accumulator once: chunk matmuls x accumulator bytes
x 2) over 1e9. What the loop's STRUCTURE moves, a plain count: the compiler
rides it on the matmul's output fusion, so it is no share of the chip's
bandwidth (PERF.md divides it by the peak and the tree's seconds in prose).
The traced tree's where a tree was traced, else the mean over the run's
trees. None when the program published no such count."""
from lib import program_counters


def read(run: dict):
    moved = program_counters.of_tree(run, "grow.hist_acc_bytes")
    return None if moved is None else moved / 1e9
