"""Median, over consecutive decode steps, of the host's time between them:
the start of an ``engine.decode_dispatch`` span minus the end of the
``engine.sample_sync`` before it, from the profiler's host plane
(``harness/hostplane.py``). It is the host's view of the device's
sampler-to-decode gap; ``hostplane.attribution`` says of what it is made.
None where the program opens no such span."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    return hostplane.median_or_none(hostplane.step_gaps_host_ms(loaded))
