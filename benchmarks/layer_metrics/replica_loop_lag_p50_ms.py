"""Median time the replica's event loop runs behind the engine's stepping
thread: the ``post_lag_us`` count of the program's ``replica.fan_out`` spans
(from the stepping thread's ``post`` of a step's tokens to the loop's
callback running), read from the profiler's host plane
(``harness/wayout.py``). Small beside long round trips means the loop is
not what a token waits for. None where the program opens no such span."""

from ..harness import hostplane, wayout

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    return wayout.count_ms(loaded, wayout.FAN_OUT, "post_lag_us") if loaded else None
