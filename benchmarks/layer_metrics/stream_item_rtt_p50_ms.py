"""Median round trip of one streamed item as the replica lives it: the
``rtt_us`` count of the program's ``replica.stream_item`` spans (from the
replica's ``yield`` of an item to the runtime asking for the next, which it
does once the item was packed, sent to its owner and acknowledged), read
from the profiler's host plane (``harness/wayout.py``). A stream's next
token waits for it, so above the step's wall a stream falls behind its row.
None where the program opens no such span."""

from ..harness import hostplane, wayout

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "ingress and router", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    return wayout.count_ms(loaded, wayout.ITEM, "rtt_us") if loaded else None
