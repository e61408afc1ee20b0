"""Host seconds ``lgb.Dataset`` construction spent before ingest, as the
program's own set-up spans timed them: the float64 copy
(``setup.dataset_to_float_s``), the float32 round-trip check
(``setup.dataset_lossless_check_s``) and bin finding
(``setup.dataset_find_bins_s``). None when the program timed none."""
from lib import program_counters

PARTS = ("setup.dataset_to_float_s", "setup.dataset_lossless_check_s",
         "setup.dataset_find_bins_s")


def read(run: dict):
    parts = [program_counters.gauge(name) for name in PARTS]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
