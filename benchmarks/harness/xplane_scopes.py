"""Device time of one jitted program by the names the program gave its
parts: ``jax.named_scope`` names and Pallas kernel names.

``harness/xplane.py`` labels a device op by its HLO name and shape, which
tells attention's matmuls from the experts' only by their sizes. What the
chip showed (PR 25): an event on the ``XLA Ops`` line carries its HLO line
as its name (``%fusion.12 = f32[64,2048]{...} fusion(...)``) and three
timing stats, and nothing of the op's metadata, so a ``jax.named_scope``
does *not* reach the trace by itself. It does reach the compiled program:
every instruction of ``compiled.as_text()`` has ``metadata={op_name=
"jit(_decode_impl)/.../moe/moe.experts/gather" ...}``, and the instruction
names there are the ones the trace's events begin with. So the scope of an
event is looked up by its instruction name in a table made from the
compiled text (``op_scopes``), which only the process that holds the chip
can make. A fusion carries the ``op_name`` of its root, so an op fused
across a scope's edge is counted on one side of it. A Pallas kernel's
``name=`` is its custom call's instruction name (``%moe_experts.8``) and
needs no table.

``by_name`` returns plain data::

    {"module": "_decode_impl", "executions": 170, "module_s": 2.1,
     "scope_s": {"moe.route": 0.2, "moe.experts": 1.1},   # self time
     "kernel_s": {"moe_experts": 0.9}}

or None where the trace has no device plane or the module never ran. A
program without these names (the parent of the PR that added them) gives
zeros, and the readers built on this return None.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from . import xplane

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_NUMBER = re.compile(r"\.\d+$")


def instruction_name(hlo_line: str) -> Optional[str]:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    found = _INSTRUCTION.match(hlo_line)
    return found["name"] if found else None


def op_scopes(compiled_text: str, scopes) -> Dict[str, str]:
    """Instruction name -> the first of ``scopes`` that is a component of
    the instruction's ``op_name``, for the instructions that have one."""
    table = {}
    for line in compiled_text.splitlines():
        name = instruction_name(line)
        found = _OP_NAME.search(line) if name else None
        if not found:
            continue
        parts = found["op_name"].split("/")
        scope = next((s for s in scopes if s in parts), None)
        if scope:
            table[name] = scope
    return table


def by_name(path: str, module: str, scope_of: Dict[str, str],
            kernels=()) -> Optional[dict]:
    """``scope_of``: ``op_scopes`` of the program ``module`` names."""
    from jax.profiler import ProfileData

    planes = sorted(
        (p for p in ProfileData.from_file(path).planes
         if xplane.DEVICE_PLANE.match(p.name)),
        key=lambda p: int(xplane.DEVICE_PLANE.match(p.name)[1]))
    if not planes:
        return None
    lines = {line.name: line for line in planes[0].lines}
    if xplane.MODULE_LINE not in lines or xplane.OP_LINE not in lines:
        return None
    runs = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for ev in lines[xplane.MODULE_LINE].events if module in ev.name)
    if not runs:
        return None
    owner = xplane._owner([(s, e, module) for s, e in runs])
    scope_events, kernel_events = [], []
    for ev in lines[xplane.OP_LINE].events:
        if owner(ev.start_ns) != module:
            continue
        span = (ev.start_ns, ev.start_ns + ev.duration_ns)
        name = instruction_name(ev.name) or ""
        base = _NUMBER.sub("", name)
        scope_events.append(span + (scope_of.get(name, "-"),))
        kernel_events.append(span + (base if base in kernels else "-",))
    scope_self = xplane.self_times(scope_events)
    kernel_self = xplane.self_times(kernel_events)
    return {
        "module": module, "executions": len(runs),
        "module_s": sum(e - s for s, e in runs) * 1e-9,
        "scope_s": {n: scope_self.get(n, 0.0) * 1e-9
                    for n in sorted(set(scope_of.values()))},
        "kernel_s": {n: kernel_self.get(n, 0.0) * 1e-9 for n in kernels},
    }
