"""Keeps ``benchmarks/tests`` runnable as cells are added.

``tests/conftest.py``'s ``tiny_benchmark`` fixture maps every cell named in
``BENCHMARK.json``'s ``workloads`` lists to a toy cell through a literal
table (``swap``) of the three cells it was written with, and raises
``KeyError`` on a fourth. That file may not be edited by the PR that adds a
cell (PERF.md, Open questions, asks a ``benchmark`` issue to make the table
a lookup with a default and to delete this hook).

Until then, two things here:

- a hook that shows that fixture a ``BENCHMARK.json`` without the cells its
  own table lacks. Which cells those are is read from the fixture's source,
  not listed here; once the fixture has no such table the hook does
  nothing. The old rehearsals so see the manifest as committed, less the
  cells they cannot map.
- ``tiny_moe_benchmark``: the manifest *as committed*, every metric and
  every cell of it, over the fixture's toys plus those of ``TOYS``. A cell
  with no toy in either table fails it by name, so a newer cell is never
  hidden from the rehearsals of the ``*_arch`` driver and its readers.
"""

import ast
import inspect
import json
import os
import textwrap
from typing import Optional

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MOE_CELL = "tiny-backlog-moe"
# real cell -> toy, for the cells newer than the fixture's own table
TOYS = {"olmoe-chat-backlog": TINY_MOE_CELL}
TOY_CONFIGS = {"tiny-moe": "benchmarks/tests/data/configs/tiny-moe.json"}
TOY_CELLS = [{"name": TINY_MOE_CELL, "config": "tiny-moe", "traffic": "tiny-backlog-moe",
              "chips": 1, "why": "test"}]


def committed() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def fixture_table(func) -> Optional[dict]:
    """The literal dict ``func``'s source assigns to ``swap``; None where
    there is none (the fixture then needs no help)."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(func)))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict)
                and any(isinstance(t, ast.Name) and t.id == "swap" for t in node.targets)):
            return ast.literal_eval(node.value)
    return None


def over_toys(bench: dict, table: dict) -> dict:
    """``bench``'s metric lists with every cell replaced by its toy."""
    unknown = {w["name"] for w in bench["workloads"]} - set(table)
    assert not unknown, (
        f"{sorted(unknown)}: no toy cell; add one under tests/data and name it in "
        "benchmarks/conftest.py TOYS")
    out = {}
    for group in ("end_to_end", "per_layer"):
        out[group] = [
            dict(m, workloads=sorted({table[w] for w in m["workloads"]}))
            if "workloads" in m else dict(m) for m in bench[group]]
    return out


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    table = fixture_table(fixturedef.func) if fixturedef.argname == "tiny_benchmark" else None
    if table is None:
        yield
        return
    request.config.stash[_TABLE] = table
    bench = committed()
    for group in ("end_to_end", "per_layer"):
        kept = []
        for metric in bench[group]:
            if "workloads" in metric:
                metric["workloads"] = [w for w in metric["workloads"] if w in table]
                if not metric["workloads"]:
                    continue
            kept.append(metric)
        bench[group] = kept
    shadow = request.getfixturevalue("tmp_path_factory").mktemp("benchmark_json")
    with open(shadow / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    # the fixture opens ROOT/BENCHMARK.json through its module's global
    patch = pytest.MonkeyPatch()
    patch.setitem(fixturedef.func.__globals__, "ROOT", str(shadow))
    try:
        yield
    finally:
        patch.undo()


_TABLE = pytest.StashKey()


@pytest.fixture
def tiny_moe_benchmark(tiny_benchmark, request):
    """``tiny_benchmark``'s toys and switched-off chip checks, under the
    metrics of ``BENCHMARK.json`` as committed (module docstring)."""
    bench = tiny_benchmark
    table = dict(request.config.stash.get(_TABLE, {}), **TOYS)
    bench.update(over_toys(committed(), table))
    bench["configs"] += [
        {"name": name, "source": "test", "reduced": [], "file": file}
        for name, file in TOY_CONFIGS.items()]
    bench["workloads"] = bench["workloads"] + TOY_CELLS
    return bench
