"""The chip's published peaks, from ``bench/peaks.json``, by device kind."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


def for_kind(kind: str) -> dict:
    with open(TABLE) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{TABLE.name}; add them with their source")
    return table[kind]


def for_device(device) -> dict:
    return for_kind(device.device_kind)
