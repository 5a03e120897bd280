"""Median length of a call of ``KVCacheManager.commit`` as the host sees
it: the duration of the program's ``kv.commit`` spans in the profiler's
host plane (``harness/hostplane.py``), an admission's and a retirement's
alike (a retirement's holds its row read, ``kv.extract_row``). While it
runs the engine's thread dispatches no decode step: with one step in flight
a call longer than that step leaves the device idle for the rest, and every
live stream's next token waits. What the call does on the device is
``kv_copy_busy_share``'s. None where the program opens no such span."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "KV manager", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    return hostplane.median_or_none(hostplane.durations_ms(loaded, "kv.commit"))
