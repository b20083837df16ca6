"""Per-phase self times of a statement's span tree.

A copy of the arithmetic of ``nebula_tpu/common/tracing.py``
``critical_path`` (PR 21 tree; the span names of PR 44's), kept here so
that no later PR can move the yardstick: each span's self time (its
duration minus the merged stretch its children cover) is charged to
its phase.  ``hop-kernel``
there is the ENQUEUE of the device work (``tpu.kernel`` is an async
launch), not device time, so it is named ``enqueue`` here; a carrier's
self time is the wait for a window or a lane seat, ``queue``.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional

PHASE_OF = {
    "tpu.mirror.build": "mirror", "tpu.absorb": "mirror",
    "tpu.peer_absorb": "mirror",
    "tpu.jit.compile": "enqueue", "tpu.launch": "enqueue",
    "tpu.kernel": "enqueue",
    "tpu.fetch": "fetch", "tpu.count": "fetch",
    "tpu.assemble": "assemble", "tpu.where": "assemble",
}
PHASES = ("queue", "mirror", "enqueue", "fetch", "assemble", "other")


def covered_us(node: dict) -> int:
    """Wall stretch of ``node`` covered by its children, interval-
    merged and clipped to the node's own window."""
    lo = node.get("start_us", 0)
    hi = lo + node.get("duration_us", 0)
    ivs = sorted((max(c.get("start_us", 0), lo),
                  min(c.get("start_us", 0) + c.get("duration_us", 0), hi))
                 for c in node.get("children", ()))
    covered, end = 0, lo
    for s, e in ivs:
        if e > max(s, end):
            covered += e - max(s, end)
            end = e
    return covered


def walk(tree: dict) -> Iterator[dict]:
    stack = list(tree.get("roots", ()))
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.get("children", ()))


def phases(tree: Optional[dict]) -> Optional[Dict[str, int]]:
    if not tree or not tree.get("roots"):
        return None
    out = dict.fromkeys(PHASES, 0)
    for node in walk(tree):
        phase = PHASE_OF.get(node.get("name"))
        if phase is None:
            phase = "queue" if node.get("children") else "other"
        out[phase] += max(node.get("duration_us", 0) - covered_us(node), 0)
    return out


def flat(trees: List[dict]) -> List[tuple]:
    """(name, start_ns, end_ns) of every span, for the gap attribution."""
    return [(n["name"], n.get("start_us", 0) * 1e3,
             (n.get("start_us", 0) + n.get("duration_us", 0)) * 1e3)
            for t in trees for n in walk(t)]
