"""Share of the chip-owning worker's compile requests that the persistent
compilation cache answered: ``cache_hits`` over ``cache_requests`` of the
program's last ``worker.startup`` record (``harness/startup.py``). Beside a
``setup_s`` it says whether that run compiled or read: a machine whose cache
lost a cell's programs reads low here, on both sides of a pair alike. None
where the program writes no such record or the cache was asked nothing."""

from ..harness import startup

META = {"unit": "%", "better": "higher", "source": "program_counter",
        "layer": "worker start-up", "moves": "setup_s"}


def read(result):
    record = startup.of(result)
    if not record or not record.get("cache_requests"):
        return None
    return 100.0 * record["cache_hits"] / record["cache_requests"]
