"""What the program's own Python costs a tree: the median, over the
window's dispatches, of ``step.host_s`` (entry to return of
``train_one_iter``: argument assembly, the jitted call's enqueue,
bookkeeping; the program times it with tracing off), in milliseconds.
The device does not wait for it unless the queue runs dry: beside
``device.idle_share`` it says whether the host could ever hold the chip
back. None on a program without the per-call record."""
import statistics

from lib import host_window


def read(run: dict):
    w = host_window.window(run)
    return None if w is None else 1000.0 * statistics.median(w[0])
