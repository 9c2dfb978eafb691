"""The yardstick for the engines' kernels: the bytes one scheduling cycle
has to move, from the cell's capacities alone, and the peaks of the chips.

The cycle is bandwidth-bound by nature (compare-and-select over tables, no
dense arithmetic to speak of), so its roofline is bytes over peak bytes/s.
The count is a floor: one pass over what the cycle is given and what it must
produce, as the capacities size them — never the program's own array sizes,
which a later PR could change.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks for `device_kind`; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add it to benchmarks/harness/peaks.json with its "
                       "source")
    return table[device_kind]


def cycle_bytes(dims: dict) -> int:
    """Bytes one cycle must read and write, 4 bytes a word:
      nodes     N x (allocatable R + used R + L label ids + K domain ids)
      existing  E x (node, class, priority)
      pending   P x (class, priority, arrival) read, P x (node) written
      classes   SC x N feasibility (1 byte) and score (4 bytes), produced once
    """
    n, p, e = dims["N"], dims["P"], dims["E"]
    nodes = n * (2 * dims["R"] + dims["L"] + dims["K"]) * 4
    existing = e * 3 * 4
    pending = p * 3 * 4 + p * 4
    classes = dims["SC"] * n * (1 + 4)
    return nodes + existing + pending + classes


def roofline_pct(dims: dict, cycles: int, busy_seconds: float,
                 device_kind: str) -> float:
    least = cycles * cycle_bytes(dims) / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / busy_seconds
