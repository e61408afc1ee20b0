"""Share of the traced window a device spent INSIDE a collective operation
(all-reduce, reduce-scatter, all-gather, collective-permute, all-to-all):
their self seconds on the device's "XLA Ops" line, where one operation runs
at a time, so this is time no compute of that core hides. An asynchronous
pair counts its ``-done`` (the wait) and not its ``-start`` (the issue). The
mean over the devices the job reduced one by one (``trace.per_device``), else
the reduced trace's own operations. None, never 0, where the trace holds no
such operation: one chip runs none."""

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
               "all-to-all")


def collective_seconds(ops):
    """Self seconds of the collective operations among ``ops``, the reduced
    trace's ``[class:category:name, seconds]`` rows; None when there is none."""
    found = None
    for key, seconds in ops:
        category = key.split(":")[1]
        if category.endswith("-start"):
            continue
        base = category[:-len("-done")] if category.endswith("-done") else category
        if base in COLLECTIVES:
            found = (found or 0.0) + seconds
    return found


def read(run: dict):
    t = run.get("trace")
    if not t or not t.get("window_s"):
        return None
    planes = list((t.get("per_device") or {}).values()) or [t]
    seconds = [collective_seconds(p.get("ops", [])) for p in planes]
    seconds = [s for s in seconds if s is not None]
    if not seconds:
        return None
    return 100.0 * (sum(seconds) / len(planes)) / t["window_s"]
