"""Median host-clock seconds of one ``Booster.update`` in the window, each
ended by ``block_until_ready``. The end-to-end rate is over all dispatches
and all time; this is the steadier statistic beside it."""
import statistics


def read(run: dict):
    d = run["spans"].get("dispatch_s")
    return statistics.median(d) if d else None
