"""The histogram kernel's share of its roofline on the rows it really
passed over: the root-pass floor (lib/work.py: 3 channels, every code
against every bin, at the chip's peak) times the traced tree's passes
(``grow.hist_rows_touched`` over the rows, the wave loop's own count), over
the device seconds the traced tree spent in the kernel's operations. The
kernel does at least the floor's work on each row it touches, so this
cannot pass 100%; beside ``kernels.hist_roofline`` it tells a slow kernel
from too many passes. None without a trace, a kernel operation in it, or
the program's count for the traced tree."""
from lib import program_counters


def read(run: dict):
    t = run.get("trace")
    if not t:
        return None
    kernel = t["class_s"]["matmul"] + t["class_s"]["custom"]
    touched = program_counters.per_tree("grow.hist_rows_touched")
    index = program_counters.traced_tree(run)
    if not kernel or not touched or index >= len(touched):
        return None
    passes = touched[index] / run["work"]["rows"]
    return 100.0 * run["work"]["root_floor_s"] * passes / kernel
