"""The histogram kernel's share of its roofline on work that is the same
whatever implements it: the root-pass floor (seconds one full pass takes at
the chip's peak, lib/work.py) over the device seconds the traced tree spent
in the kernel's operations. The kernel does more than one root pass a tree,
so this cannot pass 100%; it rises as passes are cut or the kernel improves.
Nothing to read (None, never 0) when the trace shows no kernel operation."""


def read(run: dict):
    t = run.get("trace")
    if not t:
        return None
    kernel = t["class_s"]["matmul"] + t["class_s"]["custom"]
    return 100.0 * run["work"]["root_floor_s"] / kernel if kernel else None
