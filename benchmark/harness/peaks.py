"""The table of peaks, keyed by ``device_kind`` (data: ``peaks.json``)."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Peak FLOP/s, HBM bytes/s and HBM bytes of ``device_kind``.  A kind
    that is not in the table is an error, never a default."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PATH}: add its "
            f"published peaks with their source before measuring on it")
    return table[device_kind]
