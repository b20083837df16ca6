"""The bytes one dispatch of the batched BFS program has to move, from
what its dispatch record says its levels did (``tpu/ell.py
make_batched_bfs_lanes_kernel``, PR 46 tree): how many levels the loop
ran, how many of them pushed, the slots all of them visited and how
many direction tables a level read.

``bfs_bytes.level_bytes`` reckons ONE table a level and every level a
sweep.  Since PR 30 a level follows its frontier, and since PR 46 a
statement can reach the two-signed program (``FIND PATH ... OVER e
BIDIRECT``), whose every pulled level sweeps BOTH direction tables:

* a PULLED level sweeps every slot of every table it reads
  (``bytes_model.pull_bytes``' sweep term: the slot's neighbour index
  and edge-type entry and one gathered frontier word row);
* a PUSHED level visits only the slots of its live rows, in every
  table it reads (``bytes_model.push_bytes``);
* EVERY level, pushed or pulled, passes once over the rows whatever
  the sides: it writes the next frontier's word row, reads and writes
  the row's depths (one int16 a lane) and writes the newly reached
  lanes' word row (``bfs_bytes.level_bytes``' row term).

``ell_shapes`` are ONE direction's buckets (the out-table has the same
rows and slots).  The record's ``slots`` are the pushed levels' live
slots plus, a pulled level, every slot of the tables it read, so what
the pushes visited is ``slots`` less the pulls' sweeps.  The least the
algorithm as written must move: a floor, bytes-bound, as the two
modules it is built from say of theirs.
"""
from __future__ import annotations

from typing import List, Optional

from .bfs_bytes import DEPTH_ITEMSIZE
from .bytes_model import push_bytes, table_slots


def dispatch_bytes(levels: Optional[int], pushed_levels: Optional[int],
                   slots: Optional[int], sides: Optional[int],
                   ell_shapes: List[List[int]], index_itemsize: int,
                   etype_itemsize: int, lanes: int) -> Optional[int]:
    """What one BFS dispatch at a lane rung of ``lanes`` lanes had to
    move.  None where the record lacks a field, or its pulls report
    fewer slots than the tables they swept hold (it counts another
    table than the harness): the reader then reads nothing rather than
    a share of the wrong bytes."""
    if None in (levels, pushed_levels, slots, sides):
        return None
    lane_bytes = lanes // 8
    pulls = levels - pushed_levels
    swept = pulls * sides * table_slots(ell_shapes)
    if pulls < 0 or slots < swept:
        return None
    rows = sum(r for r, _ in ell_shapes)
    return swept * (index_itemsize + etype_itemsize + lane_bytes) \
        + push_bytes(slots - swept, index_itemsize, etype_itemsize,
                     lane_bytes) \
        + levels * rows * (2 * lane_bytes + 2 * lanes * DEPTH_ITEMSIZE)
