"""The whole step's share of the chip's peak, counting only the work no
histogram GBDT can avoid: one full root pass per tree (lib/work.py), over the
window's seconds per tree. Implementation-independent, so it still bounds a
claim after a later PR replaces the kernel. Cannot pass 100%."""


def read(run: dict):
    spans, work = run["spans"], run["work"]
    trees = run["counters"].get("trees")
    if not trees or not spans.get("window_s"):
        return None
    return 100.0 * work["root_floor_s"] / (spans["window_s"] / trees)
