"""The chip's peaks, and the bytes a step has to move, from shapes.

``peaks.json`` holds each chip's published peaks, keyed by JAX's
``device_kind``. A device that is not in it is an error: no default.
"""
from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PEAKS.name}")
    return table[device_kind]


def saat_step_bytes(postings: int, rows: int, width_slots: int, k: int,
                    posting_bytes: int) -> float:
    """Least HBM bytes the SAAT steps had to move for the real rows served.

    ``postings``: the postings the rows had to read, each row's
    ``min(its terms' postings, rho)``, at ``posting_bytes`` each (the doc id).
    Per row, the query it reads (``width_slots`` term ids and weights over
    all rows, 4 bytes each) and the answer it writes (k scores and ids, 4
    bytes each). The plan's segment table is left out: a lower bound.
    """
    return float(postings) * posting_bytes + width_slots * 8.0 + rows * k * 8.0
