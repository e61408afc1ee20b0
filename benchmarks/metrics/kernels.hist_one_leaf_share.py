"""Of the rows the histogram kernel passed over, the share it passed over in
its ONE-LEAF form: ``grow.hist_rows_one_leaf`` (the touched rows of the
waves whose pending leaves numbered one: the root's pass and its smaller
child's) over ``grow.hist_rows_touched``. How often the cheaper form of the
chunk matmul engages, from the wave loop's own record of its predicate
(``WaveStats.one_leaf``). The traced tree's where a tree was traced, else
the mean over the run's trees. None when the program published no such
count (a program from before the form)."""
from lib import program_counters


def read(run: dict):
    one_leaf = program_counters.of_tree(run, "grow.hist_rows_one_leaf")
    touched = program_counters.of_tree(run, "grow.hist_rows_touched")
    if one_leaf is None or not touched:
        return None
    return 100.0 * one_leaf / touched
