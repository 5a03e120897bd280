"""Median time a decode slot stood free before the request that took it:
the ``slot_free_us`` count of the program's ``engine.admit`` spans, read
from the profiler's host plane (``harness/hostplane.py``). In a closed loop
with a client a slot a free slot is the scheduler's doing (its request is
done in the engine and not yet with its client, or the next one is on its
way in), not the traffic's. Of a request's admissions only the last counts
(one the block pool held back opens ``engine.admit`` every step until it
gets its blocks), and a slot's first, which the program writes as 0, does
not. None where the program writes no such count."""

from ..harness import hostplane

META = {"unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "engine scheduler", "moves": "out_tok_per_s"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    taken = {}  # request -> the count of its last admission, spans by start
    for span in hostplane.named(loaded, "engine.admit"):
        if "slot_free_us" in span["stats"]:
            taken[span["stats"].get("request_id")] = span["stats"]["slot_free_us"]
    return hostplane.median_or_none(
        [us / 1000.0 for us in taken.values() if us > 0])
