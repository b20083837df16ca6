"""The bytes the hops of one tick record have to move when a hop may
read BOTH direction tables (``GO ... BIDIRECT``: a two-signed OVER
set), reckoned as ``bytes_model.py`` reckons a one-sided hop.

``bytes_model.visited_bytes`` takes ``ell_shapes`` for the table a pull
sweeps; that is one direction's buckets (``deploy.py`` reads the
in-table's, and the out-table has the same rows and slots), so a
two-sided pull's second table lands among its pushed slots and is
charged at a push's rate.  Here a record says how many tables its hops
read (a stream is one OVER set, so its hops are two-sided exactly when
``hop_onesided`` is 0), and a pull moves, for EACH table it sweeps, the
slot's neighbour index, its edge-type entry and one gathered frontier
word row, and ONCE, whatever the sides, the read and write of the two
resident carriers for every table row.  A push is ``bytes_model``'s: it
visits the slots of the live rows in every table it reads and reports
them.  The count is of the table, not of the reach: a sweep that skips
padding (``hop_swept``) moves less and reads as less time against the
same bytes, as every roofline here does.
"""
from __future__ import annotations

from typing import List, Optional

from .bytes_model import push_bytes, table_slots


def pull_bytes(ell_shapes: List[List[int]], sides: int,
               index_itemsize: int, etype_itemsize: int,
               lane_bytes: int) -> int:
    """One pull over ``sides`` tables of ``ell_shapes`` each."""
    rows = sum(r for r, _ in ell_shapes)
    return sides * table_slots(ell_shapes) \
        * (index_itemsize + etype_itemsize + lane_bytes) \
        + rows * 4 * lane_bytes


def sides_of(hops: int, onesided: Optional[int]) -> int:
    """The tables a record's hops read: 2 where none of them was
    one-sided, else 1 (a program from before the field reads one)."""
    return 2 if hops and onesided == 0 else 1


def visited_bytes(hops: Optional[int], pushes: Optional[int],
                  slots: Optional[int], onesided: Optional[int],
                  ell_shapes: List[List[int]], index_itemsize: int,
                  etype_itemsize: int, lane_bytes: int) -> Optional[int]:
    """What the hops one tick record reports had to move.  None where
    the record says nothing of its hops, or its pulls report fewer
    slots than the tables they swept hold: the reader then reads
    nothing rather than a share of the wrong bytes."""
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
