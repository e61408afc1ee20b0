"""The program's share of set-up before the first dispatch: the gauges
``setup.dataset_construct_s`` (``lgb.Dataset.construct``: bin finding, the
ingest check, host binning where it runs, metadata) and
``setup.booster_init_s`` (the booster: mesh, objective, layout, device
ingest, placement), the two wholes its set-up spans tile. The rest of
``setup_s`` is the harness's (process start, data, the warm-up
dispatches). None on a program that times neither."""
from lib import program_counters


def read(run: dict):
    parts = [program_counters.gauge(name) for name in
             ("setup.dataset_construct_s", "setup.booster_init_s")]
    if any(p is None for p in parts):
        return None
    return sum(parts)
