"""The chip-owning worker's own account of its start: the program's
``worker.startup`` record (``ray_tpu.util.tracing``), which it writes into
any profiler session as an instant region of the ``/host:CPU`` plane, with
its counts as the event's stats: whole microseconds from the kernel's start
of the process (``main_us``, ``register_us``, ``wait_us``, ``backend_us``,
``weights_us``, ``engine_us``, ``other_us``, which add up to ``ready_us``)
and the compile totals at the moment of writing (``compile_us``,
``trace_lower_us``, ``programs``, ``cache_requests``, ``cache_hits``). The
worker writes it again and again (every 32nd turn of the engine's stepping
thread, every ``train.report``); the last one in the trace carries set-up's
whole compile bill, the window compiling nothing.

``hostplane.load`` keeps only the step spans' names, so this reads the
plane itself. A program that writes no such record (the parent of the PR
that added it) gives None, and every ``startup_*`` reader built on this
returns None.

    python -m benchmarks.harness.startup <file.xplane.pb>

prints the record.
"""

from __future__ import annotations

import functools
import json
import sys
from typing import Optional

from . import hostplane

NAME = "worker.startup"
PHASES = ("main_us", "register_us", "wait_us", "backend_us", "weights_us",
          "engine_us", "other_us")


@functools.lru_cache(maxsize=4)
def load(path: str) -> Optional[dict]:
    """The stats of the last ``worker.startup`` event of a trace file."""
    from jax.profiler import ProfileData

    last = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name != hostplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == NAME and (last is None or ev.start_ns >= last[0]):
                    last = (ev.start_ns, dict(ev.stats))
    return last[1] if last else None


def of(result: dict) -> Optional[dict]:
    """``load`` of this run's trace; None without a traced run, a trace file
    or a record in it."""
    path = hostplane.path_of(result)
    return load(path) if path else None


def seconds(result: dict, *keys: str) -> Optional[float]:
    """The sum of the record's ``keys`` in seconds; None where the record
    or any of them is missing (a phase that did not happen is absent)."""
    record = of(result)
    if record is None or any(k not in record for k in keys):
        return None
    return sum(record[k] for k in keys) / 1e6


if __name__ == "__main__":
    print(json.dumps(load(sys.argv[1]), indent=1, default=str))
