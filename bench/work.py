"""Work the algorithm needs, from the configuration alone, and the peaks
it is measured against."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def epoch_hbm_bytes(m: int, d: int, nnz: int) -> int:
    """Lower bound on HBM bytes one DSO epoch moves, whatever the layout:
    every nonzero's float32 value read once (indices left out: an exact
    encoding can shrink them), w, gw, alpha and ga each read and written
    once, and y read once."""
    return 4 * nnz + 16 * (m + d) + 4 * m


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    """The peak table's row for this device kind; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}: have {sorted(kinds)}")
    return kinds[device_kind]
