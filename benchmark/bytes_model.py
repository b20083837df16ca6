"""The bytes a continuous hop has to move, from the table shapes, the
slots the hop visited and the direction tables it read.

The benchmark's own copy of the repo's ``tpu/ell.py dense_hop_bytes``
idea (PR 21 tree), with what that model leaves out put in.  A hop
takes one of two ways through the ELL tables (the program chooses on
the device and reports which, the slots it visited and whether it read
one direction's table or both, in the tick record):

* a PULL sweeps every slot of every table it reads: for each it reads
  the slot's neighbour index and edge-type entry and gathers one
  frontier word row (``lane_bytes`` bytes), and ONCE, whatever the
  sides, for every table row reads and writes the two resident
  carriers (frontier and UPTO accumulator);
* a PUSH visits only the slots of the live rows, in every table it
  reads: for each it reads the index and the edge-type entry and reads
  and writes the target's word row.

``ell_shapes`` are ONE direction's buckets (``deploy.py`` reads the
in-table's, and the out-table has the same rows and slots).  A stream
is one OVER set, so the hops of one tick record are two-sided exactly
when its ``hop_onesided`` is 0 (``GO ... BIDIRECT``); a program from
before the field reads one table.

Both are the least the algorithm as written must move; the device
moves more (gathers fetch whole memory lines, a push also counts the
frontier and zeroes a carrier).  The count is of the table, not of the
reach: a sweep that skips padding (``hop_swept``) moves less and reads
as less time against the same bytes.  The roofline share built on them
is therefore bytes-bound and a floor.
"""
from __future__ import annotations

from typing import List, Optional


def table_slots(ell_shapes: List[List[int]]) -> int:
    return sum(rows * width for rows, width in ell_shapes)


def pull_bytes(ell_shapes: List[List[int]], sides: int,
               index_itemsize: int, etype_itemsize: int,
               lane_bytes: int) -> int:
    """One pull over ``sides`` tables of ``ell_shapes`` each."""
    rows = sum(r for r, _ in ell_shapes)
    return sides * table_slots(ell_shapes) \
        * (index_itemsize + etype_itemsize + lane_bytes) \
        + rows * 4 * lane_bytes


def push_bytes(slots: int, index_itemsize: int, etype_itemsize: int,
               lane_bytes: int) -> int:
    """The pushes that visited ``slots`` slots between them."""
    return slots * (index_itemsize + etype_itemsize + 2 * lane_bytes)


def sides_of(hops: int, onesided: Optional[int]) -> int:
    """The tables a record's hops read: 2 where none of them was
    one-sided, else 1 (a program from before the field reads one)."""
    return 2 if hops and onesided == 0 else 1


def visited_bytes(hops: Optional[int], pushes: Optional[int],
                  slots: Optional[int], onesided: Optional[int],
                  ell_shapes: List[List[int]], index_itemsize: int,
                  etype_itemsize: int, lane_bytes: int) -> Optional[int]:
    """What the hops one tick record reports had to move: ``hops`` of
    them, ``pushes`` of those pushes, ``slots`` visited by all (a pull
    reports those of the tables it swept).  None where the record says
    nothing of its hops, or its pulls report fewer slots than the
    tables they swept hold (it counts another table than the harness):
    the reader then reads nothing rather than a share of the wrong
    bytes."""
    if hops is None or pushes is None or slots is None:
        return None
    sizes = (index_itemsize, etype_itemsize, lane_bytes)
    sides = sides_of(hops, onesided)
    pulls = hops - pushes
    pushed = slots - pulls * sides * table_slots(ell_shapes)
    if pulls < 0 or pushed < 0:
        return None
    return pulls * pull_bytes(ell_shapes, sides, *sizes) \
        + push_bytes(pushed, *sizes)


def peak_for(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a "
                       f"row to benchmark/peaks.json with its source")
    return peaks[device_kind]
