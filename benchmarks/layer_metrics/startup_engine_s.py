"""Building a serving replica's engine (the block pool's manager, the model's
jitted programs as Python objects, the stepping thread's state; device
memory comes with the first admission): ``engine_us`` of the program's
``worker.startup`` record (``harness/startup.py``). None where the program
writes no such record."""

from ..harness import startup

META = {"unit": "s", "better": "lower", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    return startup.seconds(result, "engine_us")
