"""The table of peaks, keyed by jax's ``device_kind``. A device that is not
in the table is an error, never a default."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"in {_PATH}: add a row with its source")
    return table[device_kind]
