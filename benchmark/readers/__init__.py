"""Per-layer metric readers, one module each, found by name:
``read(select, record) -> float or None``.  None means "nothing to
read": the harness leaves the metric out of the result line and names
it on stderr (a reader of what a later PR adds to the program reads
nothing on that PR's parent).  A reader never returns 0 for a share of
a roofline or of a peak."""
from __future__ import annotations

import statistics
from typing import List, Optional

from ..spans import walk


def reduce_values(values: List[float], how: str) -> Optional[float]:
    """median / mean / p95 of a non-empty list, else None."""
    if not values:
        return None
    if how == "median":
        return float(statistics.median(values))
    if how == "mean":
        return float(statistics.fmean(values))
    if how == "p95":        # the value 5 % of the sample lie above
        ordered = sorted(values)
        return float(ordered[min(len(ordered) - 1,
                                 int(0.95 * len(ordered)))])
    raise ValueError(f"unknown reduction {how!r}")


def trees_with(record: dict, requires_span: Optional[str]) -> List[dict]:
    """The window's span trees, only those that hold ``requires_span``
    (the way a reader picks the traversal statements) when given."""
    trees = record["trees"]
    if requires_span is None:
        return trees
    return [t for t in trees
            if any(n["name"] == requires_span for n in walk(t))]
