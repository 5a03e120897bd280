"""The reduction from a profiler trace (``*.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A TPU
trace has one plane per chip (``/device:TPU:<n>``) whose lines include
``XLA Modules`` (one event per execution of a jitted program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO op, nested
where an op such as ``while`` contains others). The program writes no names
of its own yet (PERF.md, Open questions), so programs are told apart by
their module names.

``reduce`` returns plain data (JSON-serialisable)::

    {"devices": 1, "busy_s": .., "extent_s": ..,
     "modules": {"jit__decode_impl": {"count": 96, "total_s": .., "median_s": ..}},
     "ops": [["jit__decode_impl/broadcast f32[16,8,4,4096,128]", seconds], ...],
             # self time, the same op of every layer under one label
     "gaps": [["jit__decode_impl->jit__decode_impl", seconds, count], ...],
     "collective_self_s": [per device], "op_busy_s": [per device]}

Seconds are summed over the trace and averaged over the chips, unless the
key says per device.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from statistics import median
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)"
)
_SUFFIX = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?(?P<name>[^\s=]+?)(?:\.\d+)? = (?P<result>.*?) [\w\-]+\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def start_trace(log_dir: str) -> None:
    """Start ``jax.profiler`` in the process that owns the chip, without the
    Python tracer: it slows the host loop it would be measuring."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def module_name(event_name: str) -> str:
    """``jit__decode_impl(1234)`` -> ``jit__decode_impl``."""
    return _SUFFIX.sub("", event_name.strip())


def op_label(event_name: str) -> str:
    """An op event carries its whole HLO line. Keep the op's name without
    its number and its result type without layouts, so that the same op of
    every layer adds up under one label:
    ``%broadcast.548 = f32[16,8,4,4096,128]{...} broadcast(...)`` ->
    ``broadcast f32[16,8,4,4096,128]``."""
    found = _OP.match(event_name)
    if not found:
        return event_name.split(" = ")[0].lstrip("%")[:80]
    result = _LAYOUT.sub("", found["result"])
    return f"{found['name']} {result}"[:80]


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per-name self time of possibly nested (start, end, name) events: an
    event's duration less what its children cover."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [end, name, self]

    def close():
        end, name, own = stack.pop()
        out[name] += max(own, 0.0)

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close()
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    while stack:
        close()
    return dict(out)


def _events(line) -> List[Tuple[float, float, str]]:
    return [
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
        for ev in line.events
    ]


def _owner(modules: List[Tuple[float, float, str]]):
    """start time -> name of the module execution that contains it."""
    starts = [m[0] for m in modules]

    def find(t: float) -> str:
        i = bisect_right(starts, t) - 1
        if i >= 0 and t < modules[i][1]:
            return module_name(modules[i][2])
        return "-"

    return find


def describe(path: str, head: int = 3) -> dict:
    """Planes, lines and a few event names: what to read by hand before
    trusting ``reduce`` on a new kind of trace."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "first": [e.name[:80] for e in events[:head]],
            }
        out[plane.name] = lines
    return out


def reduce(path: str, top: int = 10) -> Optional[dict]:
    """See the module docstring. None where the trace has no device plane."""
    from jax.profiler import ProfileData

    planes = [
        p for p in ProfileData.from_file(path).planes if DEVICE_PLANE.match(p.name)
    ]
    if not planes:
        return None
    n = len(planes)
    busy, extent = 0.0, 0.0
    module_runs: Dict[str, List[float]] = defaultdict(list)
    op_self: Dict[str, float] = defaultdict(float)
    gap_total: Dict[str, float] = defaultdict(float)
    gap_count: Dict[str, int] = defaultdict(int)
    collective_self, op_busy = [], []
    for plane in sorted(planes, key=lambda p: int(DEVICE_PLANE.match(p.name)[1])):
        lines = {line.name: line for line in plane.lines}
        modules = sorted(_events(lines[MODULE_LINE])) if MODULE_LINE in lines else []
        ops = _events(lines[OP_LINE]) if OP_LINE in lines else []
        running = ops or modules
        if not running:
            collective_self.append(0.0)
            op_busy.append(0.0)
            continue
        plane_busy = union_s([(s, e) for s, e, _ in running])
        busy += plane_busy
        op_busy.append(plane_busy)
        extent = max(
            extent, max(e for _, e, _ in running) - min(s for s, _, _ in running))
        for s, e, name in modules:
            module_runs[module_name(name)].append(e - s)
        owner = _owner(modules)
        own = self_times(
            [(s, e, f"{owner(s)}/{op_label(name)}") for s, e, name in ops])
        coll = 0.0
        for name, seconds in own.items():
            op_self[name] += seconds
            if COLLECTIVE.match(name.split("/", 1)[1]):
                coll += seconds
        collective_self.append(coll)
        for (_, e0, n0), (s1, _, n1) in zip(modules, modules[1:]):
            if s1 > e0:
                key = f"{module_name(n0)}->{module_name(n1)}"
                gap_total[key] += s1 - e0
                gap_count[key] += 1
    return {
        "devices": n,
        "busy_s": busy / n,
        "extent_s": extent,
        "modules": {
            name: {"count": len(runs) // n if len(runs) >= n else len(runs),
                   "total_s": sum(runs) / n, "median_s": median(runs)}
            for name, runs in sorted(module_runs.items())
        },
        "ops": [
            [name, seconds / n]
            for name, seconds in sorted(op_self.items(), key=lambda kv: -kv[1])[:top]
        ],
        "gaps": [
            [name, seconds / n, gap_count[name] // n if gap_count[name] >= n
             else gap_count[name]]
            for name, seconds in sorted(gap_total.items(), key=lambda kv: -kv[1])[:top]
        ],
        "collective_self_s": collective_self,
        "op_busy_s": op_busy,
    }


def module_total_s(reduced: dict, *needles: str) -> float:
    """Seconds in modules whose name contains any of ``needles``."""
    return sum(
        m["total_s"] for name, m in reduced["modules"].items()
        if any(needle in name for needle in needles)
    )
