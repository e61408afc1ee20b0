"""GB one tree moved through the wave loop's collectives, as the program
counted them: its per-tree ``comm.bytes.<collective>`` records (the
``comm.bytes_per_wave.*`` payloads of parallel/comm.py times the waves the
loop itself counted; the root's scalar psum once), summed over the
collectives and over 1e9. A plain count of payload, no share of a peak: the
peaks table has no interconnect figure. The traced tree's where a tree was
traced, else the mean over the run's trees. None where the program counted
none (one chip, or a program from before the counters)."""
from lib import program_counters

COLLECTIVES = ("psum_scatter_hist", "allgather_splits", "psum_root_scalars",
               "psum_leaf_counts", "psum_votes", "psum_gain_ranks",
               "psum_selected_hist")


def read(run: dict):
    moved = [program_counters.of_tree(run, "comm.bytes." + name) for name in COLLECTIVES]
    moved = [m for m in moved if m is not None]
    return sum(moved) / 1e9 if moved else None
