"""The benchmark's own fast tests, inside tier-1.

The tier-1 command collects ``tests/`` only, so this module brings in the
tests (and fixtures) of every module under ``perfbench/tests`` except
``test_cells.py``: the contract of the result line, the plain reference,
the span, CPU and counter readers, the proxies, ``Cluster.stop``, the
small-I/O and storage-bench cells' drivers on an in-process fabric. A change to
the program that breaks what the benchmark reads of it then fails here,
on the CPU, before a chip run does. ``test_cells.py`` stays out: every
case of it boots a whole cluster and runs a window (minutes);
``python -m pytest perfbench/tests`` still runs it.

All the names land in ONE namespace, so two modules defining the same
test or fixture name would silently drop one of them: no two do today, and the
import below refuses (at collection) a later collision rather than
losing a test.
"""

import importlib
import os
import sys

import pytest
from _pytest.fixtures import getfixturemarker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODULES = (
    "test_churn_pieces",
    "test_cluster_stop",
    "test_contract",
    "test_proxies",
    "test_readers",
    "test_rebuild_pieces",
    "test_reference",
    "test_sb_pieces",
    "test_span_cpu",
    "test_span_ms",
    "test_trace",
    "test_uring_pieces",
)

pytest.register_assert_rewrite(
    *(f"perfbench.tests.{m}" for m in MODULES))

_origin = {}
for _m in MODULES:
    _mod = importlib.import_module(f"perfbench.tests.{_m}")
    for _name, _obj in vars(_mod).items():
        # the module's own tests, test classes and fixtures; its helpers
        # stay where the tests look them up, in the module's own globals
        if (getattr(_obj, "__module__", None) != _mod.__name__
                or not (_name.startswith(("test_", "Test"))
                        or getfixturemarker(_obj) is not None)):
            continue
        assert _name not in _origin, (
            f"{_name} is defined by both perfbench/tests/{_origin[_name]}.py"
            f" and {_m}.py: one of them would not run here")
        _origin[_name] = _m
        globals()[_name] = _obj


def test_every_fast_module_of_perfbench_is_listed():
    """A new test module under perfbench/tests joins tier-1 by being
    named above (or is test_cells.py, which does not fit)."""
    here = os.path.join(REPO, "perfbench", "tests")
    found = {f[:-3] for f in os.listdir(here)
             if f.startswith("test_") and f.endswith(".py")}
    assert found - {"test_cells"} == set(MODULES)
    assert any(n.startswith("test_") for n in _origin)
