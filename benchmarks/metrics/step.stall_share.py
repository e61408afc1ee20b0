"""Of the window's seconds, the share lost to stalled dispatches, by the
program's own entry-to-entry record (``step.host_s[i] + step.gap_s[i +
1]``): the excess over the median of every dispatch longer than 1.5 x the
median, over the sum of the dispatches. 0 in a run without a stall; one
dispatch 1.7 s over the median in a 45 s window reads 3.8. It says how
much of a run's ``train_rate`` a stall took, where the harness's clock
only shows that the rate moved; whether the stall was inside ``update()``
or in the caller's block is ``step.host_ms`` against the gap (PERF.md).
The window's last dispatch is left out (``lib/host_window``). None on
a program without the per-call record, or a window of one dispatch."""
import statistics

from lib import host_window

STALL = 1.5     # x the median


def read(run: dict):
    w = host_window.window(run)
    if w is None or not w[1]:
        return None
    lengths = w[1]
    median = statistics.median(lengths)
    excess = sum(s - median for s in lengths if s > STALL * median)
    return 100.0 * excess / sum(lengths)
