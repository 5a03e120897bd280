"""Median time a request waited in the engine's queue for a slot: the
``queue_wait_us`` count of each request's last ``engine.admit`` span (one
the block pool held back opens the span every step until it gets its
blocks), read from the profiler's host plane (``harness/wayout.py``). The
way in's last link; the count is older than this reader. None where the
program opens no such span."""

from ..harness import hostplane, wayout

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    return hostplane.median_or_none(wayout.queue_waits_ms(loaded)) if loaded else None
