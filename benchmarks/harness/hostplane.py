"""What the host did while the device waited: the program's step spans in
the profiler's own trace, reduced beside the device planes they share a
clock with.

The serving path opens a region at every boundary of a step
(``ray_tpu.util.tracing.annotate_device_trace``): ``replica.stream_next``,
``engine.lock_wait``, ``engine.step`` and, inside it, ``engine.admit``
(``kv.acquire``, ``engine.prefill``, ``kv.commit``, ``kv.insert_row``,
``kv.assemble``), ``engine.decode_dispatch``, ``engine.sample_sync``,
``engine.emit`` (``kv.commit`` with ``tail=1`` around ``kv.extract_row``).
They land in the ``/host:CPU`` plane, one line a thread, with their counts
as the event's stats. A program that opens none (the parent of the PR that
added them) gives an empty list, and every reader built on this returns
None.

``load(path)`` parses a trace once and gives::

    {"spans": [{"name", "thread", "start", "end", "stats"}, ...],  # by start
     "modules": [(start, end, name), ...]}   # first device plane, by start

with times in whole picoseconds from the session's start, so that sums are
exact. ``self_times``, ``attribution`` and the ``*_ms`` functions reduce it;

    python -m benchmarks.harness.hostplane <file.xplane.pb>

prints the per-span self time and the attribution of the device's idle time
as a table.
"""

from __future__ import annotations

import functools
import os
import sys
from bisect import bisect_right
from collections import defaultdict
from statistics import median
from typing import Dict, List, Optional, Tuple

from . import manifest, xplane

HOST_PLANE = "/host:CPU"
PREFIXES = ("engine.", "replica.", "kv.")
STEP, LOCK_WAIT = "engine.step", "engine.lock_wait"
LOCK_HANDOFF, ENGINE_IDLE = "lock_handoff", "engine_idle"
PS = 1000  # picoseconds in a nanosecond; the profiler gives float nanoseconds


def _ps(ns: float) -> int:
    return round(ns * PS)


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    from jax.profiler import ProfileData

    spans: List[dict] = []
    modules: List[Tuple[int, int, str]] = []
    device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append({
                            "name": ev.name, "thread": thread,
                            "start": _ps(ev.start_ns),
                            "end": _ps(ev.start_ns + ev.duration_ns),
                            "stats": dict(ev.stats)})
        found = xplane.DEVICE_PLANE.match(plane.name)
        if found and (device is None or int(found[1]) < device):
            device = int(found[1])
            modules = sorted(
                (_ps(ev.start_ns), _ps(ev.start_ns + ev.duration_ns),
                 xplane.module_name(ev.name))
                for line in plane.lines if line.name == xplane.MODULE_LINE
                for ev in line.events)
    spans.sort(key=lambda s: (s["start"], -s["end"]))
    return {"spans": spans, "modules": modules}


def path_of(result: dict) -> Optional[str]:
    """This run's trace: under ``benchmarks/out/<cell>/trace`` of the cell
    whose configuration and mix the result carries (a driver may have added
    keys of its own, ``_``-prefixed, to the mix)."""
    if not result.get("trace") or "config" not in result or "mix" not in result:
        return None
    mix = {k: v for k, v in result["mix"].items() if not k.startswith("_")}
    for entry in manifest.benchmark()["workloads"]:
        try:
            cell = manifest.cell(entry["name"])
        except (StopIteration, OSError):
            continue  # an entry whose files are not there is not this run's
        if cell["config_file"] == result["config"] and cell["traffic_file"] == mix:
            return xplane.find_xplane(
                os.path.join(manifest.BENCH_DIR, "out", cell["name"], "trace"))
    return None


def of(result: dict) -> Optional[dict]:
    """``load`` of this run's trace; None without a traced run, a trace file
    or a single step span in it."""
    path = path_of(result)
    loaded = load(path) if path else None
    return loaded if loaded and loaded["spans"] else None


def named(loaded: dict, name: str) -> List[dict]:
    return [s for s in loaded["spans"] if s["name"] == name]


def self_times(loaded: dict) -> Dict[str, float]:
    """Seconds a span was the innermost open one on its thread, by name."""
    by_thread: Dict[int, list] = defaultdict(list)
    for s in loaded["spans"]:
        by_thread[s["thread"]].append((s["start"], s["end"], s["name"]))
    out: Dict[str, float] = defaultdict(float)
    for events in by_thread.values():
        for name, own in xplane.self_times(events).items():
            out[name] += own / (PS * 1e9)
    return dict(out)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return [(s, e) for s, e in out]


def _overlap(sorted_disjoint: List[Tuple[int, int]], start: int, end: int) -> int:
    """Length of [start, end) inside a sorted list of disjoint intervals."""
    i = max(bisect_right(sorted_disjoint, (start, start)) - 1, 0)
    total = 0
    while i < len(sorted_disjoint) and sorted_disjoint[i][0] < end:
        total += max(
            min(end, sorted_disjoint[i][1]) - max(start, sorted_disjoint[i][0]), 0)
        i += 1
    return total


def idle_intervals(loaded: dict) -> List[Tuple[int, int]]:
    """The stretches between the first module's start and the last one's
    end in which no XLA module runs on the device."""
    busy = _union([(s, e) for s, e, _ in loaded["modules"]])
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def _stepping(loaded: dict) -> List[Tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces, by start: the innermost span open
    on the thread that has ``engine.step`` open. The engine lock admits one
    such thread at a time."""
    by_thread: Dict[int, list] = defaultdict(list)
    for s in loaded["spans"]:
        by_thread[s["thread"]].append(s)
    pieces: List[Tuple[int, int, str]] = []
    for spans in by_thread.values():  # each by start, outer before inner
        stack: List[dict] = []
        at = 0
        for s in spans + [None]:
            # close what ended before this span opens (all of it at the end)
            while stack and (s is None or stack[-1]["end"] <= s["start"]):
                at = _piece(pieces, stack, at, stack[-1]["end"])
                stack.pop()
            if s is not None:
                at = _piece(pieces, stack, at, s["start"])
                stack.append(s)
    return sorted(pieces)


def _piece(pieces: list, stack: List[dict], at: int, until: int) -> int:
    """The stretch [at, until) belongs to the innermost open span; kept
    where ``engine.step`` is among the open ones."""
    if until > at and any(s["name"] == STEP for s in stack):
        pieces.append((at, until, stack[-1]["name"]))
    return max(at, until)


def _lock_waits(loaded: dict) -> List[Tuple[int, int]]:
    return _union([(s["start"], s["end"]) for s in named(loaded, LOCK_WAIT)])


def attribution(loaded: dict) -> Dict[str, float]:
    """Seconds of the device's idle time by what the host was doing: the
    innermost span of the stepping thread, or, where no thread is inside
    ``engine.step``, ``lock_handoff`` (some thread is asking for the lock:
    it is free, or its holder is outside a step) or ``engine_idle`` (nobody
    is). The values add up to the idle time exactly."""
    pieces = _stepping(loaded)
    starts = [p[0] for p in pieces]
    waits = _lock_waits(loaded)
    out: Dict[str, int] = defaultdict(int)
    for start, end in idle_intervals(loaded):
        covered = 0
        i = max(bisect_right(starts, start) - 1, 0)
        free: List[Tuple[int, int]] = []
        at = start
        while i < len(pieces) and pieces[i][0] < end:
            a, b = max(pieces[i][0], at), min(pieces[i][1], end)
            if b > a:
                out[pieces[i][2]] += b - a
                covered += b - a
                if a > at:
                    free.append((at, a))
                at = b
            i += 1
        if at < end:
            free.append((at, end))
        handoff = sum(_overlap(waits, a, b) for a, b in free)
        out[LOCK_HANDOFF] += handoff
        out[ENGINE_IDLE] += (end - start) - covered - handoff
    return {name: ps / (PS * 1e9) for name, ps in out.items() if ps}


def idle_s(loaded: dict) -> float:
    return sum(e - s for s, e in idle_intervals(loaded)) / (PS * 1e9)


def _ms(ps: float) -> float:
    return ps / (PS * 1e6)


def lock_handoffs_ms(loaded: dict) -> List[float]:
    """Between each ``engine.step`` and the next (whatever their threads):
    the time in which somebody was asking for the lock."""
    steps = sorted(named(loaded, STEP), key=lambda s: s["start"])
    waits = _lock_waits(loaded)
    return [
        _ms(_overlap(waits, a["end"], b["start"]) if b["start"] > a["end"] else 0)
        for a, b in zip(steps, steps[1:])
    ]


def step_gaps_host_ms(loaded: dict) -> List[float]:
    """For each ``engine.decode_dispatch`` but the first: its start minus the
    end of the ``engine.sample_sync`` before it, which is when the host
    learned that the last decode step was over."""
    syncs = sorted(s["end"] for s in named(loaded, "engine.sample_sync"))
    gaps = []
    for d in named(loaded, "engine.decode_dispatch"):
        i = bisect_right(syncs, d["start"]) - 1
        if i >= 0:
            gaps.append(_ms(d["start"] - syncs[i]))
    return gaps


def sync_lags_ms(loaded: dict) -> List[float]:
    """For each ``engine.sample_sync``: its end minus the end of the last
    decode module that ended before it. Small and steady where the host and
    the device planes share a clock."""
    ends = [e for _, e, name in loaded["modules"] if "_decode_impl" in name]
    lags = []
    for s in named(loaded, "engine.sample_sync"):
        i = bisect_right(ends, s["end"]) - 1
        if i >= 0 and ends[i] >= s["start"]:
            lags.append(_ms(s["end"] - ends[i]))
    return lags


def durations_ms(loaded: dict, name: str) -> List[float]:
    return [_ms(s["end"] - s["start"]) for s in named(loaded, name)]


def counts(loaded: dict, name: str, key: str) -> List[float]:
    return [s["stats"][key] for s in named(loaded, name) if key in s["stats"]]


def median_or_none(values: List[float]) -> Optional[float]:
    return median(values) if values else None


def table(loaded: dict) -> str:
    spans = loaded["spans"]
    own = self_times(loaded)
    calls: Dict[str, int] = defaultdict(int)
    for s in spans:
        calls[s["name"]] += 1
    lines = [f"{'span':<26}{'count':>8}{'self s':>12}{'self ms each':>14}"]
    for name, seconds in sorted(own.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<26}{calls[name]:>8}{seconds:>12.6f}"
                     f"{1000 * seconds / calls[name]:>14.4f}")
    idle = idle_s(loaded)
    lines.append("")
    lines.append(f"device idle between modules: {idle:.6f} s in "
                 f"{len(idle_intervals(loaded))} gaps, attributed to")
    for name, seconds in sorted(attribution(loaded).items(), key=lambda kv: -kv[1]):
        share = 100 * seconds / idle if idle else 0.0
        lines.append(f"{name:<26}{seconds:>12.6f} s{share:>8.2f} %")
    for label, values in (
            ("engine.lock_wait ms", durations_ms(loaded, LOCK_WAIT)),
            ("lock handoff ms", lock_handoffs_ms(loaded)),
            ("step gap on the host ms", step_gaps_host_ms(loaded)),
            ("sync end after decode ms", sync_lags_ms(loaded)),
            ("executor wait ms", [
                us / 1000 for us in counts(loaded, "replica.stream_next",
                                           "executor_wait_us")]),
            ("queue wait ms", [
                us / 1000 for us in counts(loaded, "engine.admit", "queue_wait_us")]),
            ("decode batch", counts(loaded, "engine.decode_dispatch", "batch"))):
        if values:
            ordered = sorted(values)
            lines.append(
                f"{label:<26} n={len(values)} p50={median(values):.3f} "
                f"p90={ordered[int(0.9 * (len(ordered) - 1))]:.3f} "
                f"max={ordered[-1]:.3f} mean={sum(values) / len(values):.3f}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(table(load(sys.argv[1])))
