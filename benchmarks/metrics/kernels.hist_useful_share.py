"""Of the rows the histogram kernel passed over, the share that belonged to
a pending leaf: ``grow.hist_rows_active`` over ``grow.hist_rows_touched``.
A streamed pass reads every row to use the pending ones; a compacted pass
wastes only the tail of its last chunk. The traced tree's where a tree was
traced, else the mean over the run's trees. None when the program
published no counts."""
from lib import program_counters


def read(run: dict):
    active = program_counters.of_tree(run, "grow.hist_rows_active")
    touched = program_counters.of_tree(run, "grow.hist_rows_touched")
    if active is None or not touched:
        return None
    return 100.0 * active / touched
