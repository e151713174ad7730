"""Published peaks of one chip, keyed by JAX's ``device_kind``
(``peaks.json``).  A kind missing from the table is an error."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PATH}")
    return table[device_kind]
