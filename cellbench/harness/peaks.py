"""Published peaks per device kind (``peaks.json``, with its source).

A device kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
