"""The serving stack's packages import downwards only: ``ops`` <- ``models``
<- ``kvcache`` <- ``llm`` <- ``serve``. An ``ast`` walk over every file of
the lower package: an import of an upper one counts wherever it stands, at
module level or inside a function."""

import ast
import pathlib

import pytest

import ray_tpu

ROOT = pathlib.Path(ray_tpu.__file__).parent


def _imported_packages(path: pathlib.Path):
    """The ``ray_tpu`` sub-packages ``path`` imports, with the line."""
    # the file's own package path, for relative imports: ray_tpu/ops/x.py
    # is in ("ray_tpu", "ops")
    package = ("ray_tpu",) + path.relative_to(ROOT).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            targets = [alias.name.split(".") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = list(package[: len(package) - node.level + 1]
                        if node.level else ())
            base += node.module.split(".") if node.module else []
            # ``from .. import models`` names the package in ``names``
            targets = [base + [alias.name] for alias in node.names]
        else:
            continue
        for parts in targets:
            if len(parts) > 1 and parts[0] == "ray_tpu":
                yield parts[1], node.lineno


@pytest.mark.parametrize("lower,uppers", [
    ("ops", ("models", "kvcache", "llm", "serve")),
    ("models", ("kvcache", "llm", "serve")),
    ("kvcache", ("llm", "serve")),
], ids=lambda v: v if isinstance(v, str) else "-".join(v))
def test_no_module_imports_a_package_above_its_own(lower, uppers):
    files = sorted((ROOT / lower).rglob("*.py"))
    assert files
    upward = [
        f"{path.relative_to(ROOT)}:{line} imports {name}"
        for path in files
        for name, line in _imported_packages(path)
        if name in uppers
    ]
    assert not upward, upward
