"""From the kernel's start of the chip-owning worker process to *ready*
(``_LLMReplica.__init__`` returned, or the training loop's function entered):
``ready_us`` of the program's ``worker.startup`` record (``harness/startup.py``).
What is left of ``setup_s`` beside it is the cluster's start before the
worker's, the check, the warm-up and the ramp. None where the program writes
no such record."""

from ..harness import startup

META = {"unit": "s", "better": "lower", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    return startup.seconds(result, "ready_us")
