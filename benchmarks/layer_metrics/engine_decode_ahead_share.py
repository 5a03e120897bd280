"""Share of decode steps dispatched while the step before them was still
unread by the host: of the program's ``engine.decode_dispatch`` spans in the
profiler's host plane (``harness/hostplane.py``) that carry an ``ahead``
count (steps in flight when this one was dispatched), those where it is 1
or more, in percent. At 100 the device always has a step queued behind the
one it runs, and the host's work of a step is hidden; the first step of an
engine found idle counts against it. None where the program opens no such
span or writes no such count (a program that reads every step before it
dispatches the next)."""

from ..harness import hostplane

META = {"unit": "%", "better": "higher", "source": "program_counter",
        "layer": "engine scheduler", "moves": "tpot_p50_ms"}


def read(result):
    loaded = hostplane.of(result)
    if not loaded:
        return None
    ahead = hostplane.counts(loaded, "engine.decode_dispatch", "ahead")
    if not ahead:
        return None
    return 100.0 * sum(1 for n in ahead if n >= 1) / len(ahead)
