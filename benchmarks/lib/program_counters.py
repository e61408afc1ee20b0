"""What the program counted and timed about itself, for the per-layer
readers: its process-wide registry (``lightgbm_tpu.observability
.get_registry()``; the job and the readers run in one process, and the
``train`` job's ``bst._ensure_finalized()`` is where the program publishes
what its wave loops counted, one observation per tree, in tree order).

Every function returns None where the program has no such record (a
program from before the counters, a booster that published nothing): a
reader then reports nothing, never 0.
"""


def _registry():
    try:
        from lightgbm_tpu.observability import get_registry
    except ImportError:
        return None
    return get_registry()


def counter(name: str):
    reg = _registry()
    if reg is None:
        return None
    return reg.snapshot()["counters"].get(name)


def gauge(name: str):
    reg = _registry()
    if reg is None:
        return None
    return reg.snapshot()["gauges"].get(name)


def per_tree(name: str):
    """The per-tree observations of ``name`` in tree order, or None. Only
    a complete record is returned: one that the registry's window has not
    started to forget."""
    reg = _registry()
    if reg is None:
        return None
    recorded = reg.snapshot().get("summaries", {}).get(name)
    if not recorded or not recorded["count"]:
        return None
    summary = reg.summary(name)
    values = summary.values()
    return values if len(values) == summary.count else None


def traced_tree(run: dict):
    """Index of the tree the profiler traced: it follows the warm-up
    dispatches. None in a run that traced nothing."""
    if not run.get("trace"):
        return None
    return len(run["info"]["warmup_s"])


def of_tree(run: dict, name: str):
    """``name`` of the traced tree where there is one, else the mean over
    the run's trees."""
    values = per_tree(name)
    if not values:
        return None
    index = traced_tree(run)
    if index is not None and index < len(values):
        return values[index]
    return sum(values) / len(values)


def rows_per_wave():
    """Rows one wave's routing pass and partition update move (the padded
    rows of one device), from the program's own counters: ``rows.routed``
    is that, summed over every wave of every tree."""
    routed, waves = counter("rows.routed"), per_tree("grow.waves")
    if not routed or not waves:
        return None
    return routed / sum(waves)
