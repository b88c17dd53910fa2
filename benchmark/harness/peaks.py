"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an error,
not a default."""
from __future__ import annotations

import json
import os
from typing import Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"benchmark/harness/peaks.json knows {sorted(table)}")
    return table[device_kind]
