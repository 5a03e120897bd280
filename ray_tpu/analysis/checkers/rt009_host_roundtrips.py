"""RT009: no ad-hoc device->host round-trips on the serving hot path.

Incident class this encodes: the tensor-parallel serving work (PR 13).
Every ``jax.device_get``/``np.asarray(jnp...)``/``float(jnp...)`` sprinkled
through the engine or the KV-cache manager is a synchronous device->host
transfer that stalls the dispatch pipeline — and under a sharded mesh it is
worse, because materializing a replicated output gathers from every device.
The serving plane therefore funnels ALL materialization through the single
audited ``host_sync`` chokepoint in ``ray_tpu/_internal/host_sync.py`` (one fused
sampling program, one transfer per decode step); everything else on the hot
path must stay on device.

Flags, in ``ray_tpu/llm/engine.py`` and ``ray_tpu/kvcache/``:

- ``jax.device_get(...)`` calls;
- ``.block_until_ready()`` calls (a barrier is a hidden round-trip);
- ``np.asarray(X)`` / ``np.array(X)`` / ``float(X)`` / ``int(X)`` where the
  argument expression is rooted at a ``jnp``/``jax`` name — i.e. the value
  being materialized is statically known to live on device. Host-side
  conversions (``np.asarray(py_list)``, ``int(host_row[i])``) are fine and
  not flagged; that asymmetry is what keeps the rule statically decidable.

The body of a function literally named ``host_sync`` is exempt: that IS the
chokepoint. Route new materializations through it so they stay auditable.
"""

from __future__ import annotations

import ast
from typing import Set

from ..core import Checker, register

_MATERIALIZERS_NP = {"asarray", "array"}
_MATERIALIZERS_BUILTIN = {"float", "int"}


def _root_name(node: ast.AST) -> str:
    """Leftmost Name of an attribute/call/subscript chain, '' otherwise."""
    while True:
        if isinstance(node, ast.Attribute):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Name):
            return node.id
        else:
            return ""


def _device_rooted(node: ast.AST) -> bool:
    return _root_name(node) in ("jnp", "jax")


def _host_sync_spans(tree: ast.AST) -> Set[int]:
    """ids of all nodes inside a FunctionDef named host_sync (the exempt
    chokepoint)."""
    exempt: Set[int] = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name == "host_sync"
        ):
            for sub in ast.walk(node):
                exempt.add(id(sub))
    return exempt


@register
class HostRoundTripChecker(Checker):
    RULE_ID = "RT009"
    DESCRIPTION = (
        "device->host round-trip on the serving hot path (engine/kvcache); "
        "route materialization through host_sync"
    )

    def applies_to(self, path: str) -> bool:
        parts = path.split("/")
        if "kvcache" in parts[:-1]:
            return True
        return parts[-1] == "engine.py" and len(parts) >= 2 and (
            parts[-2] == "llm"
        )

    def check_file(self, path, tree, source):
        exempt = _host_sync_spans(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or id(node) in exempt:
                continue
            func = node.func
            if isinstance(func, ast.Attribute):
                if func.attr == "device_get" and _root_name(func) == "jax":
                    yield self.finding(
                        path, node,
                        "jax.device_get() on the serving hot path is a "
                        "synchronous device->host transfer; route it "
                        "through host_sync",
                    )
                    continue
                if func.attr == "block_until_ready":
                    yield self.finding(
                        path, node,
                        ".block_until_ready() on the serving hot path is "
                        "a hidden dispatch barrier; drop it or move it "
                        "behind host_sync",
                    )
                    continue
                if (
                    func.attr in _MATERIALIZERS_NP
                    and _root_name(func) == "np"
                    and node.args
                    and _device_rooted(node.args[0])
                ):
                    yield self.finding(
                        path, node,
                        f"np.{func.attr}() of a device value materializes "
                        "it host-side mid-hot-path; route it through "
                        "host_sync",
                    )
                    continue
            elif isinstance(func, ast.Name):
                if (
                    func.id in _MATERIALIZERS_BUILTIN
                    and node.args
                    and _device_rooted(node.args[0])
                ):
                    yield self.finding(
                        path, node,
                        f"{func.id}() of a device value is a synchronous "
                        "device->host transfer; route it through host_sync",
                    )
