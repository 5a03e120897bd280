"""The first initialisation of the JAX backend in the chip-owning worker (the
chip attach, on as many chips as the cell has): ``backend_us`` of the
program's ``worker.startup`` record (``harness/startup.py``). None where the
program writes no such record."""

from ..harness import startup

META = {"unit": "s", "better": "lower", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    return startup.seconds(result, "backend_us")
