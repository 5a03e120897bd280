"""A serving replica's weights, from whichever source (seeded on the device,
the weight plane, a blob), until they are on the device: ``weights_us`` of the
program's ``worker.startup`` record (``harness/startup.py``). None where the
program writes no such record, and in a worker that makes its weights inside
a program of its own (a training job)."""

from ..harness import startup

META = {"unit": "s", "better": "lower", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    return startup.seconds(result, "weights_us")
