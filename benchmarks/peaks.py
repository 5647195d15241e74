"""The table of peaks, keyed by ``device_kind`` as JAX reports it. A device
that is not in the table is an error, never a default."""

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PATH} (known: "
            f"{sorted(table)}); add a sourced row, do not guess")
    return table[device_kind]
