"""The window's dispatches by the program's own per-call record
(``step.host_s``, ``step.gap_s``, ``step.gc_s``: one observation a call
of the step, in call order, timed with tracing off), shared by
``step.host_ms``, ``step.stall_share`` and ``step.gc_share``. The
window's calls are found in the series as
``program_counters.traced_tree`` finds the traced tree, by position: the
warm-up dispatches, the traced one where there is one, then the window's,
then the steady tree."""
from lib import program_counters


def window(run: dict):
    """``(host, length, gc)`` of the window's dispatches, or None on a
    program without the record. ``host[i]`` is ``step.host_s`` of dispatch
    i; ``length[i]`` its entry to the next entry (``step.host_s[i] +
    step.gap_s[i + 1]``: the call, then the caller's block on the device)
    and ``gc[i]`` the collector's seconds in that interval
    (``step.gc_s[i + 1]``). The window's LAST dispatch has no length: the
    gap after it holds the score's fetch, not a block."""
    series = [program_counters.per_tree(name)
              for name in ("step.host_s", "step.gap_s", "step.gc_s")]
    if not all(series):
        return None
    host_s, gap_s, gc_s = series
    n = len(run["spans"].get("dispatch_s") or [])
    first = len(run["info"]["warmup_s"]) + (1 if run.get("trace") else 0)
    if not n or min(map(len, series)) < first + n:
        return None
    calls = range(first, first + n)
    whole = calls[:-1]
    return ([host_s[k] for k in calls],
            [host_s[k] + gap_s[k + 1] for k in whole],
            [gc_s[k + 1] for k in whole])
