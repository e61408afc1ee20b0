"""Waves the wave loop ran for one tree, as the loop itself counted them on
the device (``grow.waves``): every wave pays a routing pass and a partition
update over all rows. The traced tree's where a tree was traced, else the
mean over the run's trees. None when the program published no count."""
from lib import program_counters


def read(run: dict):
    return program_counters.of_tree(run, "grow.waves")
