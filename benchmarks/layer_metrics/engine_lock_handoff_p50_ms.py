"""Median, over consecutive ``engine.step`` spans, of the time between them
in which some thread was asking for the engine lock (``hostplane``'s
``lock_handoff`` state): what passing the lock, and the interpreter, from
one of the replica's pool threads to the next costs a step. None where the
program opens no such span."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    return hostplane.median_or_none(hostplane.lock_handoffs_ms(loaded))
