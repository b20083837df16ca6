"""The plain reference: the same statements on the same data, answered
from the generated arrays with numpy and nothing of the program.

This module holds what every statement shape shares: the CSR over the
labelled edge list, and the comparison of answers (an order-free
digest and exact multiset equality).  What ONE shape of statement
means is a module of its own, ``semantics/<kind>.py``, found by the
``kind`` a traffic file's ``semantics`` gives:

    answer(graph, semantics, key) -> Answer
    ARITY = 2       # optional: vertices in one statement's key (1)

so a deployment brings its statement shapes as new files.  ``key`` is
one vertex label where ARITY is 1 and a tuple of ARITY labels where it
is more.

An answer is held in one of two forms: numeric columns (int64 arrays,
compared as sorted row arrays or by an order-free digest) or, where a
column is not numeric, a sorted list of tuples.
"""
from __future__ import annotations

import importlib
from typing import List, Tuple, Union

import numpy as np

Answer = Union[Tuple[np.ndarray, ...], List[tuple]]
_M1, _M2 = np.uint64(0x9E3779B97F4A7C15), np.uint64(0xC2B2AE3D27D4EB4F)


def semantics_module(kind: str):
    """``semantics/<kind>.py``; a kind with no such file is an error
    that names the file to add."""
    name = f"{__package__}.semantics.{kind}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"the reference has no semantics {kind!r}: add "
                         f"benchmark/semantics/{kind}.py") from None


class Graph:
    """CSR over the labelled edge list (vertex labels are small
    positive integers, so they index the row pointer directly), with
    the edge property table."""

    def __init__(self, src: np.ndarray, dst: np.ndarray,
                 edge_prop_table: List[dict], edge_prop_idx: np.ndarray):
        order = np.argsort(src, kind="stable")
        self.dst = dst[order]
        self.eidx = edge_prop_idx[order]
        self.etable = edge_prop_table
        top = int(max(src.max(initial=0), dst.max(initial=0))) + 1
        self.ptr = np.zeros(top + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=top), out=self.ptr[1:])
        self.deg = np.diff(self.ptr)

    def frontier(self, start: int, hops: int) -> np.ndarray:
        """The set of vertices reached after ``hops`` hops."""
        frontier = np.asarray([start], np.int64)
        for _ in range(hops):
            seen = np.zeros(len(self.deg), bool)
            seen[self.dst[self.edge_positions(frontier)]] = True
            frontier = np.nonzero(seen)[0]
        return frontier

    def edge_positions(self, frontier: np.ndarray) -> np.ndarray:
        n = self.deg[frontier]
        total = int(n.sum())
        if total == 0:
            return np.zeros(0, np.int64)
        starts = np.repeat(self.ptr[frontier]
                           - np.concatenate(([0], np.cumsum(n)[:-1])), n)
        return starts + np.arange(total)

    def answer(self, semantics: dict, key) -> Answer:
        return semantics_module(semantics["kind"]).answer(
            self, semantics, key)


def n_rows(ans: Answer) -> int:
    return len(ans) if isinstance(ans, list) else \
        (len(ans[0]) if ans else 0)


def digest(ans: Answer) -> tuple:
    """Order-free fingerprint of a row multiset: the row count and, for
    numeric columns, two independent 64-bit sums over a per-row mix
    (wrapping arithmetic); a tuple list is its own fingerprint."""
    n = n_rows(ans)
    if n == 0:
        return ("empty",)
    if isinstance(ans, list):
        return ("rows", tuple(sorted(ans)))
    mix = np.zeros(n, np.uint64)
    with np.errstate(over="ignore"):
        for c, col in enumerate(ans):
            mix = (mix + (col.astype(np.uint64) + np.uint64(c + 1)) * _M1) \
                * _M2
            mix ^= mix >> np.uint64(29)
        return ("cols", n, int(mix.sum(dtype=np.uint64)),
                int((mix * _M1 ^ (mix >> np.uint64(31))).sum(
                    dtype=np.uint64)))


def same_rows(got: Answer, want: Answer) -> bool:
    """Exact multiset equality (sorted rows)."""
    if n_rows(got) == 0 or n_rows(want) == 0:
        return n_rows(got) == n_rows(want)
    if isinstance(got, list) or isinstance(want, list):
        return isinstance(got, list) and isinstance(want, list) \
            and sorted(got) == sorted(want)
    if len(got) != len(want) or n_rows(got) != n_rows(want):
        return False
    a, b = (np.stack(x, axis=1) for x in (got, want))
    a = a[np.lexsort(a.T[::-1])]
    b = b[np.lexsort(b.T[::-1])]
    return bool(np.array_equal(a, b))
