"""Seconds the program's device ingest took (its own report,
``gbdt._ingest_report["seconds"]``): binning and packing the raw rows on the
device, H2D feed included. None when device ingest did not engage."""


def read(run: dict):
    return run["spans"].get("ingest_s")
