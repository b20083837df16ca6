"""The bytes a continuous hop has to move, from the table shapes and
the slots the hop visited.

The benchmark's own copy of the repo's ``tpu/ell.py dense_hop_bytes``
idea (PR 21 tree), with what that model leaves out put in.  A hop
takes one of two ways through the ELL table (the program chooses on
the device and reports which, and the slots it visited, in the tick
record):

* a PULL sweeps every slot of the table: for each it reads the slot's
  neighbour index and edge-type entry and gathers one frontier word
  row (``lane_bytes`` bytes), and for every table row reads and writes
  the two resident carriers (frontier and UPTO accumulator);
* a PUSH visits only the slots of the live rows: for each it reads the
  index and the edge-type entry and reads and writes the target's word
  row.

Both are the least the algorithm as written must move; the device
moves more (gathers fetch whole memory lines, a push also counts the
frontier and zeroes a carrier).  The roofline share built on them is
therefore bytes-bound and a floor.
"""
from __future__ import annotations

from typing import List, Optional


def table_slots(ell_shapes: List[List[int]]) -> int:
    return sum(rows * width for rows, width in ell_shapes)


def hop_bytes(ell_shapes: List[List[int]], index_itemsize: int,
              etype_itemsize: int, lane_bytes: int) -> int:
    """One pull: the whole table."""
    rows = sum(r for r, _ in ell_shapes)
    return table_slots(ell_shapes) \
        * (index_itemsize + etype_itemsize + lane_bytes) \
        + rows * 4 * lane_bytes


def push_bytes(slots: int, index_itemsize: int, etype_itemsize: int,
               lane_bytes: int) -> int:
    """The pushes that visited ``slots`` slots between them."""
    return slots * (index_itemsize + etype_itemsize + 2 * lane_bytes)


def visited_bytes(hops: Optional[int], pushes: Optional[int],
                  slots: Optional[int], ell_shapes: List[List[int]],
                  index_itemsize: int, etype_itemsize: int,
                  lane_bytes: int) -> Optional[int]:
    """What the hops one tick record reports had to move: ``hops`` of
    them, ``pushes`` of those pushes, ``slots`` visited by all (a pull
    reports the table's).  A record that says nothing of its hops (a
    program from before it reported them) counts one whole sweep.  A
    record whose pulls report fewer slots than the loaded table has is
    counting another table than the harness: None, and the reader reads
    nothing rather than a share of the wrong bytes."""
    sizes = (index_itemsize, etype_itemsize, lane_bytes)
    if hops is None or pushes is None or slots is None:
        return hop_bytes(ell_shapes, *sizes)
    pulls = hops - pushes
    pushed = slots - pulls * table_slots(ell_shapes)
    if pulls < 0 or pushed < 0:
        return None
    return pulls * hop_bytes(ell_shapes, *sizes) + push_bytes(pushed, *sizes)


def peak_for(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add a "
                       f"row to benchmark/peaks.json with its source")
    return peaks[device_kind]
