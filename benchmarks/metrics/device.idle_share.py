"""1 - (union of the device's leaf operation intervals) / traced window."""


def read(run: dict):
    t = run.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
