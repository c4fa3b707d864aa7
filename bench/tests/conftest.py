"""Sizes a test run holds for configurations that ``test_bench_correct``'s
own table (``SMALL_GRAPH``) does not list yet.  Each keeps its
configuration's routing: email-eu stays dense enough for the planner to
pick the closure regime on the CPU (label mass over its budget, the
closure's working set under the fallback budget), with a top share of
degrees that the smaller hyperedge count can draw.

The table is found through the collected tests' own module objects, so
it is reached under any import mode; a collected ``test_bench_correct``
without a ``SMALL_GRAPH`` table stops the run here, by name."""
from pathlib import Path

import pytest

SMALL_GRAPHS = {"email-eu": {"n": 60, "m": 3500, "degrees": {
    "law": "power", "top_share": 0.12}}}


def pytest_collection_modifyitems(session, config, items):
    modules = {id(item.module): item.module for item in items
               if Path(str(item.fspath)).name == "test_bench_correct.py"}
    for module in modules.values():
        table = getattr(module, "SMALL_GRAPH", None)
        if not isinstance(table, dict):
            raise pytest.UsageError(
                f"{module.__name__} has no SMALL_GRAPH table to add the "
                f"sizes of {sorted(SMALL_GRAPHS)} to")
        for name, graph in SMALL_GRAPHS.items():
            table.setdefault(name, graph)
