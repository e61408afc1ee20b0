"""Of the window's seconds, the share Python's collector ran: the sum of
``step.gc_s[i + 1]`` (the collector's seconds from dispatch i's entry to
the next entry, timed by the program's one ``gc.callbacks`` hook) over
the window's dispatches, over the sum of their entry-to-entry lengths by
the same record. Beside ``step.stall_share`` it says whether a stall was
the collector's. The window's last dispatch is left out
(``lib/host_window``). None on a program without the per-call record,
or a window of one dispatch."""
from lib import host_window


def read(run: dict):
    w = host_window.window(run)
    if w is None or not w[1]:
        return None
    return 100.0 * sum(w[2]) / sum(w[1])
