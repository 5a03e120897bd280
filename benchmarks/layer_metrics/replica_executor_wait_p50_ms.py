"""Median wait of a stream's ``next()`` for one of the replica's pool
threads: the ``executor_wait_us`` count of the program's
``replica.stream_next`` spans (from ``run_in_executor`` on the replica's
loop to the call starting in a thread), read from the profiler's host plane
(``harness/hostplane.py``). None where the program opens no such span."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    waits = hostplane.counts(loaded, "replica.stream_next", "executor_wait_us")
    return hostplane.median_or_none([us / 1000.0 for us in waits])
