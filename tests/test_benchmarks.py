"""Tier-1's view of the benchmark's own tests.

`benchmarks/tests/` is collected by `python -m pytest benchmarks/tests`
and not by the tier-1 command, which names `tests/`. This file loads
each of its test files and takes their tests and fixtures as its own,
case by case, so that a program change which breaks a reader, a driver
or the manifest fails tier-1. It does what `benchmarks/tests/conftest.py`
does for the paths, and changes neither that file nor the command."""

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
for _p in (str(BENCH.parent), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# last, for the benchmark's test files that import one another's
# helpers: a module of this directory with the same name still wins
if str(BENCH / "tests") not in sys.path:
    sys.path.append(str(BENCH / "tests"))

_taken: dict[str, str] = {}
for _file in sorted((BENCH / "tests").glob("test_*.py")):
    _spec = importlib.util.spec_from_file_location(
        "benchmarks_tests_" + _file.stem, _file)
    _mod = importlib.util.module_from_spec(_spec)
    sys.modules[_spec.name] = _mod
    _spec.loader.exec_module(_mod)
    for _name, _obj in vars(_mod).items():
        if _name.startswith("_"):
            continue
        if getattr(_obj, "__module__", None) == _spec.name:
            # tests, fixtures and helpers the file defines itself: two
            # files with one name for a test would hide one of them
            assert _name not in _taken, (
                f"{_name} is defined by {_taken[_name]} and {_file.name}")
            _taken[_name] = _file.name
        globals()[_name] = _obj
