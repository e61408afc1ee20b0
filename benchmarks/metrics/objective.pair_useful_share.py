"""Of the pair slots one step of the ranking objective evaluates, the share
that carries a lambda: the counter ``rank.pairs`` (pairs of one query whose
labels differ, counted once on the host when the objective is set up) over
``rank.pair_slots`` (over the length classes, padded queries x M x M, the
classes' padding to whole blocks included), in %. What padding each query
to a power of two, padding a class to whole blocks and evaluating every pair
of equal labels leaves of the work. None on a program without the counters
(every objective but lambdarank, and a program from before them)."""
from lib import program_counters


def read(run: dict):
    pairs = program_counters.counter("rank.pairs")
    slots = program_counters.counter("rank.pair_slots")
    if pairs is None or not slots:
        return None
    return 100.0 * pairs / slots
