"""Open loop: requests are sent on a seeded schedule at a rate fixed in the
mix file, whether or not earlier ones have finished. Independent users: the
queue can grow, and a request is timed from when it was due.

Mix keys: ``arrivals`` (``harness/traffic.py``), ``ramp_s``, ``grace_s``.
"""

from __future__ import annotations

import threading
import time

from ..harness import traffic
from .serve_common import run_serving


def _load(clients, mix, vocab, seed, start, end, clock):
    source = traffic.requests(mix, vocab, seed)
    threads = []
    for due in traffic.arrival_times(mix["arrivals"], start, end, seed):
        request = next(source)  # made before its time, not during it
        delay = due - clock()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=clients.one, args=(request, due), daemon=True)
        t.start()
        threads.append(t)
    time.sleep(max(end - clock(), 0))
    return threads


def run(run):
    return run_serving(run, _load)
