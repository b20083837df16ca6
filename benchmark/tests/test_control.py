"""The two tests the contract asks to keep beside the benchmark
(run: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``; the
benchmark's own runs do not run them).

1. The control: the plain reference put in the program's place with one
   stated guarantee broken ("every answer is the exact row multiset"):
   one row dropped, one row duplicated.  The system states no numeric
   precision, answers are exact, so the limit is 0 and the control must
   come out as not correct.
2. The rest of a run, driven without the harness's look for a chip,
   with the timed path broken underneath (an answer altered where the
   program produces it): ``correct`` must come out false; unbroken, the
   same drive must come out true.
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, selfcheck  # noqa: E402
from benchmark.semantics.go import go  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _graph(seed: int) -> reference.Graph:
    rng = np.random.default_rng(seed)
    n, m = 400, 4000
    key = np.unique(rng.integers(0, n, m) * n + rng.integers(0, n, m))
    src, dst = key // n + 1, key % n + 1
    return reference.Graph(src, dst, [{"w": i} for i in range(7)],
                           np.arange(len(src)) % 7)


@pytest.mark.parametrize("seed", [11, 2_345_678_901, 3_999_999_999])
@pytest.mark.parametrize("weaken", ["drop", "duplicate"])
def test_control_is_not_correct(seed, weaken):
    g = _graph(seed)
    sound = failed = 0
    for start in range(1, 40):
        want = go(g, start, 2, ["_dst", "w"])
        if not reference.n_rows(want):
            continue
        again = tuple(c[::-1] for c in want)        # sound: another order
        assert reference.digest(again) == reference.digest(want)
        assert reference.same_rows(again, want)
        sound += 1
        bad = tuple(c[1:] for c in want) if weaken == "drop" else \
            tuple(np.append(c, c[0]) for c in want)
        assert reference.digest(bad) != reference.digest(want)
        assert not reference.same_rows(bad, want)
        failed += 1
    assert sound and failed == sound


def test_comparator_and_recorded_trace():
    assert selfcheck.check_comparator() == []
    assert selfcheck.check_recorded_trace() == []


def _drive(monkeypatch, break_answers: bool) -> dict:
    from benchmark import run
    spec = run.load_json(ROOT, "BENCHMARK.json")
    parts = run.resolve(spec, spec["workloads"][0]["name"])
    if break_answers:
        # the answer altered where the program produces it: the serving
        # path's result transport loses its last row on the way out
        from nebula_tpu.graph.interim import ColumnarRows
        real = ColumnarRows.to_wire

        def broken(self):
            if self._rows is None and self._n > 1:
                return real(ColumnarRows([c[:-1] for c in self._cols],
                                         self._n - 1))
            return real(self)
        monkeypatch.setattr(ColumnarRows, "to_wire", broken)
    return run.run_cell(parts, seed=3_000_000_019, seconds=2.0,
                        trace=False, device=CPU, tiny=True)


def test_a_run_with_the_timed_path_broken_is_not_correct(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = _drive(monkeypatch, break_answers=True)
    assert out["correct"] is False and out["failed"] > 0


def test_the_same_drive_unbroken_is_correct(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = _drive(monkeypatch, break_answers=False)
    assert out["correct"] is True and out["failed"] == 0
