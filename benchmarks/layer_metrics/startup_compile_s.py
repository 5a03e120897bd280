"""What the chip-owning worker had spent making programs when it last wrote
its ``worker.startup`` record inside the traced stretch: ``compile_us`` (the
backend compiler, reads of the persistent cache included) plus
``trace_lower_us`` (tracing and lowering). The window compiles nothing, so
this is set-up's whole bill; most of it comes after ``startup_ready_s``, in
the check that is also the warm-up. None where the program writes no such
record."""

from ..harness import startup

META = {"unit": "s", "better": "lower", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    return startup.seconds(result, "compile_us", "trace_lower_us")
