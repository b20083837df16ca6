"""The bytes one per-lane count of the resident frontier has to move,
from the table shapes (``tpu/ell.py make_lane_count_kernel``, PR 33
tree), reckoned as ``bytes_model.py`` reckons a hop.

The count answers the leavers of a tick whose statement is a k-hop
neighbourhood count: it reads the packed frontier's word row of every
real vertex (``lanes`` / 8 bytes a row: 16 B at the 128-lane rung) and
writes one int32 a lane.  A real vertex is a table row that is no hub
extra row and no growth spare (those hold partial ORs a pull left
behind, and the pad row nothing: the program does not read them).
That is the least the algorithm as written must move; the device moves
more where it keeps a 16-byte-wide row in a wider memory tile, so the
roofline share built on it is bytes-bound and a floor.  The count
costs the same for 1 counting leaver as for 128: the bytes are those
of the lane rung, not of the lanes counted.
"""
from __future__ import annotations

from typing import List

COUNT_ITEMSIZE = 4      # one int32 a lane comes back


def vertex_rows(ell_shapes: List[List[int]], hub_rows: int) -> int:
    """The table's rows that are vertices: all but the hub extra rows
    and growth spares (``facts["ell_hub_rows"]``)."""
    return sum(rows for rows, _ in ell_shapes) - hub_rows


def count_bytes(ell_shapes: List[List[int]], hub_rows: int,
                lanes: int) -> int:
    """One count at a lane rung of ``lanes`` lanes."""
    return vertex_rows(ell_shapes, hub_rows) * (lanes // 8) \
        + lanes * COUNT_ITEMSIZE
