"""The bytes one level of the batched BFS program has to move, from
the table shapes (``tpu/ell.py make_batched_bfs_lanes_kernel``, PR 27
tree), reckoned as ``bytes_model.py`` reckons a pull.

A level is one sweep of the whole ELL table and one pass over the depth
matrix: for every slot the sweep reads the neighbour index and the
edge-type entry and gathers one packed frontier word row (``lanes`` / 8
bytes); for every table row it writes the next frontier's word row,
reads and writes the row's depths (one int16 a lane) and writes the
newly reached lanes' word row.  That is the least the algorithm as
written must move; the device moves more (gathers fetch whole memory
lines, the hub rows are merged in a pass of their own), so the roofline
share built on it is bytes-bound and a floor.  The sweep costs the same
for 1 lane used as for all of them: the bytes are those of the lane
rung, not of the lanes used.
"""
from __future__ import annotations

from typing import List

from .bytes_model import table_slots

DEPTH_ITEMSIZE = 2      # the depth matrix is int16 until the loop ends


def level_bytes(ell_shapes: List[List[int]], index_itemsize: int,
                etype_itemsize: int, lanes: int) -> int:
    """One BFS level at a lane rung of ``lanes`` lanes."""
    lane_bytes = lanes // 8
    rows = sum(r for r, _ in ell_shapes)
    return table_slots(ell_shapes) \
        * (index_itemsize + etype_itemsize + lane_bytes) \
        + rows * (2 * lane_bytes + 2 * lanes * DEPTH_ITEMSIZE)
