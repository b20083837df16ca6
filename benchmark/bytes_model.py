"""The bytes one continuous hop has to move, from the table shapes.

The benchmark's own copy of the repo's ``tpu/ell.py dense_hop_bytes``
idea (PR 21 tree), with what that model leaves out put in: one packed
hop reads, for every ELL slot, the slot's neighbour index and edge-type
entry and gathers one frontier word row (``lane_bytes`` bytes), and for
every table row reads and writes the two resident carriers (frontier
and UPTO accumulator).  It is the least the algorithm as written must
move; the device moves more (gathers fetch whole memory lines).  The
roofline share built on it is therefore bytes-bound and a floor.
"""
from __future__ import annotations

from typing import List


def hop_bytes(ell_shapes: List[List[int]], index_itemsize: int,
              etype_itemsize: int, lane_bytes: int) -> int:
    slots = sum(rows * width for rows, width in ell_shapes)
    rows = sum(r for r, _ in ell_shapes)
    return slots * (index_itemsize + etype_itemsize + lane_bytes) \
        + rows * 4 * lane_bytes


def peak_for(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a "
                       f"row to benchmark/peaks.json with its source")
    return peaks[device_kind]
