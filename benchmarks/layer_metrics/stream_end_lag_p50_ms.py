"""Median time a finished answer lay in its stream's inbox before the
stream's coroutine took it: over requests, the ``inbox_wait_us`` count of
their ``replica.stream_end`` span, read from the profiler's host plane
(``harness/wayout.py``). The coroutine
was still awaiting the acknowledgement of earlier tokens; in a closed loop
the slot the answer left stands free that long at least. None where the
program opens no such span."""

from ..harness import hostplane, wayout

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "ingress and router", "moves": "out_tok_per_s"}


def read(result):
    loaded = hostplane.of(result)
    return hostplane.median_or_none(wayout.end_lags_ms(loaded)) if loaded else None
