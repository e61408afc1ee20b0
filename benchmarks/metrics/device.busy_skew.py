"""A straggling shard: the largest minus the smallest of the devices' busy
seconds in the traced window, over the window (``trace.per_device``, each
plane reduced on its own by the job). The devices wait for each other at
every collective, so what one shard does longer than the rest the others
spend idle. None with fewer than two traced devices."""


def read(run: dict):
    t = run.get("trace")
    planes = (t or {}).get("per_device") or {}
    if len(planes) < 2 or not t.get("window_s"):
        return None
    busy = [p["busy_s"] for p in planes.values()]
    return 100.0 * (max(busy) - min(busy)) / t["window_s"]
