"""Median time a thread that wants to step the engine waits for the engine
lock: the duration of the program's ``engine.lock_wait`` spans in the
profiler's host plane (``harness/hostplane.py``). Every stream's ``next()``
takes the lock and runs a whole step, so this, not prefill, is most of a
chat request's time to first token. None where the program opens no such
span."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    return hostplane.median_or_none(
        hostplane.durations_ms(loaded, hostplane.LOCK_WAIT))
