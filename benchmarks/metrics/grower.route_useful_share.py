"""Of the rows that the routing pass and the partition update pass over,
the share that belonged to a leaf that split: ``grow.rows_split`` over
``grow.waves`` times the rows one wave moves (``rows.routed`` per wave).
Both run over every row every wave, whatever the wave splits. The traced
tree's where a tree was traced, else the mean over the run's trees. None
when the program published no counts."""
from lib import program_counters


def read(run: dict):
    split = program_counters.of_tree(run, "grow.rows_split")
    waves = program_counters.of_tree(run, "grow.waves")
    rows = program_counters.rows_per_wave()
    if split is None or not waves or not rows:
        return None
    return 100.0 * split / (waves * rows)
