"""Host seconds of bin finding inside ``lgb.Dataset`` construction: the row
sample, the per-column copies and every feature's quantiles, as the
program's set-up span ``dataset.find_bins`` timed them (gauge
``setup.dataset_find_bins_s``; part of ``setup.dataset_s``). Linear in the
columns: 2,000 here where criteo67 has 67. None when the program timed
none."""
from lib import program_counters


def read(run: dict):
    return program_counters.gauge("setup.dataset_find_bins_s")
