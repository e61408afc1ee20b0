"""Of the slots the split scan ran over, the share that held a leaf:
``grow.scan_slots_pending`` over ``grow.scan_slots``. The scan covers
2 x hist_slots histograms every wave (the pending leaves and their siblings
by subtraction), whatever the wave holds: one of 50 at the root. The traced
tree's where a tree was traced, else the mean over the run's trees. None
when the program published no counts."""
from lib import program_counters


def read(run: dict):
    held = program_counters.of_tree(run, "grow.scan_slots_pending")
    slots = program_counters.of_tree(run, "grow.scan_slots")
    if held is None or not slots:
        return None
    return 100.0 * held / slots
