"""Rows the histogram kernel passed over for one tree, in root passes:
``grow.hist_rows_touched`` (all rows for a streamed pass, whole chunks of
the pending rows for a compacted one, as the wave loop recorded them) over
the table's rows. The floor of ``kernels.hist_roofline`` counts 1. The
traced tree's where a tree was traced, else the mean over the run's trees.
None when the program published no counts."""
from lib import program_counters


def read(run: dict):
    touched = program_counters.of_tree(run, "grow.hist_rows_touched")
    if touched is None:
        return None
    return touched / run["work"]["rows"]
