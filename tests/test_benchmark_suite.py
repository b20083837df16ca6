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
