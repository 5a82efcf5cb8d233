"""Published peaks of one chip, keyed by JAX's ``device_kind``
(bench/peaks.json). A device that is not in the table is an error."""
import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PATH}")
    return table[device_kind]
