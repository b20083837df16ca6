"""Lets tier-1 run the benchmark's own tests (``benchmark/tests``: the
control, the broken-path drive, the harness, each statement shape's
reference against brute force), so that what a PR adds beside the
benchmark is guarded by the driver's run.  Every test and fixture
defined in a ``benchmark/tests/test_*.py`` is bound here under its own
name, so each still counts; two tests of one name would hide one, and
are refused at collection.
"""
import glob
import importlib
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

for _path in sorted(glob.glob(os.path.join(
        os.path.dirname(_HERE), "benchmark", "tests", "test_*.py"))):
    _module = importlib.import_module(
        f"benchmark.tests.{os.path.basename(_path)[:-3]}")
    for _name, _obj in vars(_module).items():
        if _name.startswith("_") \
                or getattr(_obj, "__module__", None) != _module.__name__:
            continue
        if _name in globals():
            raise ImportError(f"benchmark/tests defines {_name} twice")
        globals()[_name] = _obj


# benchmark/tests/test_bidir.py holds graph500-s20-bidir and its cell to
# be the LAST entries of BENCHMARK.json's lists (``spec["workloads"][-1]``,
# ``spec["configs"][-1]``: true when PR 40 wrote it).  A PR that adds a
# deployment appends to those lists and may edit no file under benchmark/
# (PR 46 did), so the test is bound here over the lists as they ended with
# that cell: everything else it holds (the kind, the traffic and the
# configuration against count16's, the per-layer lists) is read from the
# file as it stands.  A ``benchmark`` PR turns the two ``[-1]`` into
# look-ups by name; this wrapper goes then (PERF.md section 7).
_bidir = importlib.import_module("benchmark.tests.test_bidir")
_held = _bidir.test_the_bidir_kind_is_found_by_name_and_its_cell_resolves


def test_the_bidir_kind_is_found_by_name_and_its_cell_resolves(monkeypatch):
    real = _bidir.run.load_json

    def as_it_ended_with_the_bidir_cell(*parts):
        spec = real(*parts)
        if parts[-1] == "BENCHMARK.json":
            for key, last, since in (
                    ("workloads", _bidir.BIDIR_CELL,
                     ["graph500-s20-bipath.bipath16"]),
                    ("configs", "graph500-s20-bidir",
                     ["graph500-s20-bipath"])):
                names = [entry["name"] for entry in spec[key]]
                cut = names.index(last) + 1
                # nothing hides behind the cut: what it takes off is PR
                # 46's one cell and one configuration, by name
                assert names[cut:] == since, (key, names[cut:])
                spec[key] = spec[key][:cut]
        return spec

    monkeypatch.setattr(_bidir.run, "load_json",
                        as_it_ended_with_the_bidir_cell)
    _held()
