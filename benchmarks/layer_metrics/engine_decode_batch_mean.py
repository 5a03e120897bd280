"""Sequences a decode step carried, as the engine counted them: the mean
``batch`` count (occupied slots) over the program's
``engine.decode_dispatch`` spans in the profiler's host plane
(``harness/hostplane.py``). ``sched_decode_batch_mean`` estimates the same
from outside. None where the program opens no such span."""

from ..harness import hostplane

META = {"unit": "seqs", "better": "higher", "source": "program_counter",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    batches = hostplane.counts(loaded, "engine.decode_dispatch", "batch")
    return sum(batches) / len(batches) if batches else None
