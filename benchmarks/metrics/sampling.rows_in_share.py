"""Of the table's rows, the share a tree was grown on: the step's own count
of its row sample (``sample.rows_in``, counted on the device where the
sample is drawn and fetched with the trees) over the table's rows. A control:
GOSS at top_rate 0.2 and other_rate 0.1 reads 30% within the draw's width,
and it moves only if the sample changed. The traced tree's where a tree was
traced, else the mean over the run's trees (the unsampled first ones
included). None when the program published no such count."""
from lib import program_counters


def read(run: dict):
    rows_in = program_counters.of_tree(run, "sample.rows_in")
    if rows_in is None:
        return None
    return 100.0 * rows_in / run["work"]["rows"]
