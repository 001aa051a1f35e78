"""The chip's published peaks, from ``peaks.json``, keyed by ``device_kind``.
The operations a frame requires are counted by its configuration's family
(``bench/configs/<family>.py``, ``frame_flops``)."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """The chip's published peak; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])
